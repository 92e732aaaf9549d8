"""Epoch-synchronized serving engine: the TVM applied to LLM serving.

The mapping to the paper's machine (§4) is exact:

  TV slot          <-> request slot (fixed batch position + its KV cache)
  task type        <-> {prefill, decode}
  fork             <-> admitting a request's first decode task (prefill
                       forks the decode chain); each decode forks its
                       successor until EOS/max_tokens
  emit             <-> completing a request (slot contents retired)
  epoch (phase 2)  <-> one bulk ``decode_step`` over *all* active slots —
                       work-together: every active task executes in one
                       dispatch, load-balanced by the batch dimension
  nextFreeCore     <-> free-slot allocation by prefix sum over the free
                       mask (kernels/fork_compact machinery; no atomics)
  phase 1/3 (CPU)  <-> admission + retirement bookkeeping on the host

Prefills are batched per epoch (bucketed padding) and their caches are
scattered into the slots they were allocated — the analogue of the paper's
coalesced TV writes at fork time.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..models.common import ModelConfig
from ..models.model import decode_step, init_cache, prefill


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # (len,) i32
    max_new_tokens: int = 32
    eos: Optional[int] = None
    # filled by the engine
    rid: int = -1
    output: Optional[List[int]] = None


def _bucket(n: int, minimum: int = 8) -> int:
    p = minimum
    while p < n:
        p *= 2
    return p


class EpochServer:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, jnp.ndarray],
        n_slots: int = 8,
        max_len: int = 256,
        enc_frames: Optional[jnp.ndarray] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = init_cache(cfg, n_slots, max_len)
        self._enc_frames = enc_frames
        if cfg.encdec:
            assert enc_frames is not None
            from ..models.model import build_cross_cache, encode

            self.cache["enc_out"] = jnp.broadcast_to(
                encode(params, cfg, enc_frames[:1]),
                (n_slots, cfg.encoder_len, cfg.d_model),
            ).astype(cfg.compute_dtype)
            ck, cv = build_cross_cache(params, cfg, self.cache["enc_out"])
            self.cache["cross_k"] = ck.astype(cfg.compute_dtype)
            self.cache["cross_v"] = cv.astype(cfg.compute_dtype)
        # host-side TV bookkeeping (paper phase 1/3 state)
        self.active = np.zeros(n_slots, bool)
        self.remaining = np.zeros(n_slots, np.int64)
        self.last_token = np.zeros(n_slots, np.int64)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self.epochs = 0
        self._rid = 0
        self._decode = jax.jit(
            lambda p, t, c: decode_step(p, cfg, t, c)
        )
        self._prefill_cache: Dict[int, object] = {}
        # (request ids, last-token logits) of the latest admission's
        # prefill, left on the device, for callers that check the first step
        self.last_prefill = None

    # ----------------------------------------------------------- frontend
    def submit(self, req: Request) -> int:
        req.rid = self._rid
        req.output = []
        self._rid += 1
        self.queue.append(req)
        return req.rid

    # ----------------------------------------------------- fork: admission
    def _admit(self):
        """Allocate free slots to queued requests by prefix sum (fork)."""
        free = ~self.active
        n_free = int(free.sum())
        n_new = min(n_free, len(self.queue))
        if n_new == 0:
            return
        # prefix-sum slot allocation: contiguous ranks over the free mask —
        # the same cooperative allocation the engine/kernels use (no atomics)
        offsets, _ = kops.fork_offsets(jnp.asarray(free, jnp.int32))
        rank = np.asarray(offsets)
        slots = np.nonzero(free & (rank < n_new))[0]
        reqs = [self.queue.pop(0) for _ in range(n_new)]

        # bulk prefill at a bucketed length (one epoch-style dispatch)
        plens = [len(r.prompt) for r in reqs]
        Lp = _bucket(max(plens))
        toks = np.zeros((n_new, Lp), np.int32)
        for i, r in enumerate(reqs):
            toks[i, : len(r.prompt)] = r.prompt  # right-pad: ragged prompts
        pf_key = (n_new, Lp)
        ef = None
        if self.cfg.encdec:
            ef = jnp.broadcast_to(
                self._enc_frames[:1],
                (n_new,) + tuple(self._enc_frames.shape[1:]),
            )
        if pf_key not in self._prefill_cache:
            cfg = self.cfg
            self._prefill_cache[pf_key] = jax.jit(
                lambda p, t, lp, ef_: prefill(
                    p, cfg, t, max_len=self.max_len, last_positions=lp,
                    enc_frames=ef_,
                )
            )
        logits, new_cache = self._prefill_cache[pf_key](
            self.params, jnp.asarray(toks),
            jnp.asarray(np.asarray(plens, np.int32) - 1), ef,
        )
        self.last_prefill = ([r.rid for r in reqs], logits)
        next_tok = np.asarray(jnp.argmax(logits, -1))

        # scatter the prefilled caches into the allocated slots (coalesced
        # TV write at fork time)
        sl = jnp.asarray(slots)
        for key in ("k", "v", "ssm_state", "ssm_conv"):
            if key in self.cache and key in new_cache:
                self.cache[key] = self.cache[key].at[:, sl].set(
                    new_cache[key].astype(self.cache[key].dtype)
                )
        self.cache["lengths"] = self.cache["lengths"].at[sl].set(
            jnp.asarray(plens, jnp.int32)
        )
        for i, r in enumerate(reqs):
            s = slots[i]
            self.active[s] = True
            self.remaining[s] = r.max_new_tokens
            self.last_token[s] = next_tok[i]
            self.slot_req[s] = r
            r.output.append(int(next_tok[i]))

    # ------------------------------------------------------------- epochs
    def step(self):
        """One serving epoch: phase 1 admit, phase 2 bulk decode, phase 3
        retire (the paper's three-phase structure)."""
        self._admit()
        if not self.active.any():
            return False
        toks = jnp.asarray(self.last_token[:, None].astype(np.int32))
        logits, self.cache = self._decode(self.params, toks, self.cache)
        self.epochs += 1
        nxt = np.asarray(jnp.argmax(logits, -1))
        for s in range(self.n_slots):
            if not self.active[s]:
                continue
            r = self.slot_req[s]
            self.remaining[s] -= 1
            tok = int(nxt[s])
            done = self.remaining[s] <= 0 or (
                r.eos is not None and tok == r.eos
            )
            if not done:
                r.output.append(tok)
                self.last_token[s] = tok
            if done:
                # emit: retire the slot (entry invalid; reclaimed by admit)
                self.active[s] = False
                self.slot_req[s] = None
                self.completed.append(r)
        return True

    def run_to_completion(self, max_epochs: int = 10_000):
        while (self.queue or self.active.any()) and self.epochs < max_epochs:
            self.step()
        return self.completed
