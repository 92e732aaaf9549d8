"""Model assembly: unified decoder-only / encoder-decoder transformer with
attn | ssm | hybrid blocks, dense or MoE FFN, scanned layer stacks, chunked
cross-entropy, and a single-token decode step over ragged caches.

The layer stack is a ``jax.lax.scan`` over stacked per-layer parameters —
keeps the HLO size O(1) in depth (95-layer deepseek-67b compiles in the same
graph size as 24-layer granite-moe) and gives remat a natural boundary.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import apply_attn, apply_attn_decode, init_attn
from .common import ModelConfig, Params, constrain, get_unroll
from .layers import (
    apply_mlp,
    apply_norm,
    embed_tokens,
    init_embeddings,
    init_mlp,
    init_norm,
    logits_fn,
)
from .moe import apply_moe, init_moe
from .ssm import apply_ssm, apply_ssm_decode, init_ssm, init_ssm_cache


# ------------------------------------------------------------------- init
def init_model(cfg: ModelConfig, key: jax.Array):
    """Returns (params: dict[name -> array], axes: dict[name -> tuple])."""
    pb = Params(key, cfg.param_dtype)
    init_embeddings(pb.scope("embed"), cfg)
    lyr = pb.scope("layers")
    if cfg.block in ("attn", "hybrid"):
        init_attn(lyr.scope("attn"), cfg)
    if cfg.block in ("ssm", "hybrid"):
        init_ssm(lyr.scope("ssm"), cfg)
    init_norm(lyr.scope("norm1"), cfg)
    has_ffn = cfg.moe is not None or (cfg.d_ff > 0 and cfg.block != "ssm")
    if has_ffn and not cfg.parallel_block:
        init_norm(lyr.scope("norm2"), cfg)
    if cfg.moe is not None:
        init_moe(lyr.scope("moe"), cfg)
    elif has_ffn:
        init_mlp(lyr.scope("mlp"), cfg)
    if cfg.encdec:
        enc = pb.scope("encoder")
        init_attn(enc.scope("attn"), cfg, n_layers=cfg.n_encoder_layers)
        init_mlp(enc.scope("mlp"), cfg)
        # encoder norms need their own layer count
        Lc = dataclasses.replace(cfg, n_layers=cfg.n_encoder_layers)
        init_norm(enc.scope("norm1"), Lc)
        init_norm(enc.scope("norm2"), Lc)
        init_norm(pb.scope("enc_final_norm"), cfg, layered=False)
        pb.add(
            "enc_pos_embed", (cfg.encoder_len, cfg.d_model),
            ("kv_seq", "embed"), scale=0.02,
        )
        init_attn(lyr.scope("cross"), cfg)
        init_norm(lyr.scope("norm_cross"), cfg)
    init_norm(pb.scope("final_norm"), cfg, layered=False)
    return pb.values, pb.axes


def _layer_windows(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """Per-layer attention window (0 = full/global), or None when every
    layer is full: the layer scan then keeps the window a static 0, which
    the Pallas attention kernels need (they specialize on it)."""
    L = cfg.n_layers
    if cfg.sliding_window <= 0:
        return None
    win = jnp.full((L,), cfg.sliding_window, jnp.int32)
    if cfg.global_layer_every > 0:
        is_global = (jnp.arange(L) % cfg.global_layer_every) == 0
        win = jnp.where(is_global, 0, win)
    return win


def _split_layer_params(params: Dict[str, Any], prefix: str = "layers/"):
    stacked = {
        k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)
    }
    rest = {k: v for k, v in params.items() if not k.startswith(prefix)}
    return stacked, rest


# ---------------------------------------------------------------- forward
def _decoder_layer(
    cfg: ModelConfig,
    p: Dict[str, Any],      # per-layer slice
    x: jnp.ndarray,         # (B, S, d)
    window,                 # static 0 or scalar i32; 0 = full
    enc_out: Optional[jnp.ndarray],
    collect_kv: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (x', aux_loss, kv) — kv nonempty only when collect_kv."""
    aux = jnp.float32(0.0)
    kv: Dict[str, jnp.ndarray] = {}
    h = apply_norm(p, "norm1", cfg, x)
    mix = jnp.zeros_like(x)
    if cfg.block in ("attn", "hybrid"):
        if collect_kv:
            a, (k_, v_) = apply_attn(
                p, "attn", cfg, h, causal=True, window=window, return_kv=True
            )
            kv["k"], kv["v"] = k_, v_
        else:
            a = apply_attn(p, "attn", cfg, h, causal=True, window=window)
        mix = mix + a
    if cfg.block in ("ssm", "hybrid"):
        if collect_kv:
            y_, st_, tail_ = apply_ssm(p, "ssm", cfg, h, return_state=True)
            kv["ssm_state"], kv["ssm_conv"] = st_, tail_
        else:
            y_ = apply_ssm(p, "ssm", cfg, h)
        mix = mix + y_
    if cfg.block == "hybrid":
        mix = 0.5 * mix
    if cfg.parallel_block and cfg.moe is None and cfg.d_ff > 0:
        mix = mix + apply_mlp(p, "mlp", cfg, h)  # attn ∥ mlp, shared norm
        x = x + mix
        return x, aux, kv
    x = x + mix
    x = constrain(x, "batch", "seq", "embed")
    if cfg.encdec and enc_out is not None:
        hc = apply_norm(p, "norm_cross", cfg, x)
        x = x + apply_attn(
            p, "cross", cfg, hc, causal=False, use_rope=False,
            kv_source=enc_out, site="kv_cross",
        )
    if cfg.moe is not None:
        h2 = apply_norm(p, "norm2", cfg, x)
        y, aux = apply_moe(p, "moe", cfg, h2)
        x = x + y
    elif cfg.d_ff > 0 and cfg.block != "ssm":
        h2 = apply_norm(p, "norm2", cfg, x)
        x = x + apply_mlp(p, "mlp", cfg, h2)
    x = constrain(x, "batch", "seq", "embed")
    return x, aux, kv


def encode(params: Dict[str, Any], cfg: ModelConfig, frames: jnp.ndarray):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend): frames (B, T_enc, d) -> (B, T_enc, d)."""
    x = frames.astype(cfg.compute_dtype)
    x = x + params["enc_pos_embed"].astype(cfg.compute_dtype)[None]
    stacked, _ = _split_layer_params(params, "encoder/")

    def body(h, pl):
        a = apply_norm(pl, "norm1", cfg, h)
        h = h + apply_attn(
            pl, "attn", cfg, a, causal=False, use_rope=False, site="kv_enc"
        )
        m = apply_norm(pl, "norm2", cfg, h)
        h = h + apply_mlp(pl, "mlp", cfg, m)
        return h, ()

    x, _ = jax.lax.scan(body, x, stacked, unroll=get_unroll("enc"))
    return apply_norm(params, "enc_final_norm", cfg, x)


def forward(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jnp.ndarray,                      # (B, S) i32
    enc_frames: Optional[jnp.ndarray] = None,  # (B, T_enc, d) for enc-dec
    remat: bool = True,
    collect_kv: bool = False,
):
    """Token ids -> final hidden states (B, S, d), plus summed MoE aux loss.
    With ``collect_kv``, also returns the stacked per-layer cache entries
    (dict of (L, ...) arrays) for prefill->decode handoff."""
    x = embed_tokens(params, cfg, tokens)
    x = constrain(x, "batch", "seq", "embed")
    enc_out = None
    if cfg.encdec:
        assert enc_frames is not None, "enc-dec model needs encoder frames"
        enc_out = encode(params, cfg, enc_frames)
    stacked, _ = _split_layer_params(params)
    wins = _layer_windows(cfg)

    def body(h, xs):
        pl, win = xs
        win = 0 if win is None else win
        h, aux, kv = _decoder_layer(cfg, pl, h, win, enc_out, collect_kv)
        return h, (aux, kv)

    if remat and not collect_kv and cfg.remat != "none":
        policy = (
            jax.checkpoint_policies.nothing_saveable
            if cfg.remat == "full"
            else jax.checkpoint_policies.checkpoint_dots
        )
        body = jax.checkpoint(body, policy=policy)
    x, (auxs, kvs) = jax.lax.scan(
        body, x, (stacked, wins), unroll=get_unroll("layer")
    )
    x = apply_norm(params, "final_norm", cfg, x)
    if collect_kv:
        return x, auxs.sum(), kvs
    return x, auxs.sum()


# ------------------------------------------------------------------- loss
def chunked_xent(
    params: Dict[str, Any],
    cfg: ModelConfig,
    hidden: jnp.ndarray,   # (B, S, d)
    labels: jnp.ndarray,   # (B, S) i32, -1 = ignore
    chunk: int = 512,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-entropy without materializing (B, S, V) logits: scan over
    sequence chunks (peak activation = B x chunk x V)."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    hp = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = hp.shape[1] // chunk
    hp = hp.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    lp = lp.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        h, y = xs
        logits = logits_fn(params, cfg, h)           # (B, c, Vp) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        yc = jnp.clip(y, 0, cfg.vocab_padded - 1)
        picked = jnp.take_along_axis(
            logits, yc[..., None], axis=-1
        )[..., 0]
        valid = (y >= 0).astype(jnp.float32)
        loss = ((lse - picked) * valid).sum()
        return (acc[0] + loss, acc[1] + valid.sum()), ()

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hp, lp),
        unroll=get_unroll("chunk"),
    )
    return tot / jnp.maximum(cnt, 1.0), cnt


def prefill(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jnp.ndarray,                       # (B, S) i32
    max_len: Optional[int] = None,
    enc_frames: Optional[jnp.ndarray] = None,
    last_positions: Optional[jnp.ndarray] = None,  # (B,) for ragged prompts
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Serving prefill: run the full prompt, return (last-token logits,
    decode cache).  This is what the ``prefill_32k`` dry-run cells lower.

    Ragged right-padded prompts: pass ``last_positions`` (= prompt_len - 1)
    and set the returned cache's ``lengths`` to the true prompt lengths —
    pad rows beyond a request's length are never read back (decode masks by
    length), so right padding is harmless."""
    B, S = tokens.shape
    max_len = max_len or S
    x, _, kvs = forward(
        params, cfg, tokens, enc_frames=enc_frames, remat=False,
        collect_kv=True,
    )
    cache: Dict[str, jnp.ndarray] = {
        "lengths": jnp.full((B,), S, jnp.int32)
    }
    if "k" in kvs:
        pad = max_len - S
        cache["k"] = jnp.pad(kvs["k"], ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        cache["v"] = jnp.pad(kvs["v"], ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    if "ssm_state" in kvs:
        cache["ssm_state"] = kvs["ssm_state"]
        cache["ssm_conv"] = kvs["ssm_conv"]
    if cfg.encdec:
        assert enc_frames is not None
        cache["enc_out"] = encode(params, cfg, enc_frames)
        cache["cross_k"], cache["cross_v"] = build_cross_cache(
            params, cfg, cache["enc_out"]
        )
    if last_positions is None:
        last = x[:, -1]
    else:
        last = x[jnp.arange(B), last_positions]
        cache["lengths"] = last_positions.astype(jnp.int32) + 1
    logits = logits_fn(params, cfg, last)
    return logits, cache


def loss_fn(
    params: Dict[str, Any],
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    aux_weight: float = 0.01,
):
    hidden, aux = forward(
        params, cfg, batch["tokens"], enc_frames=batch.get("enc_frames")
    )
    loss, n_tok = chunked_xent(params, cfg, hidden, batch["labels"])
    total = loss + aux_weight * aux
    return total, dict(xent=loss, aux=aux, n_tokens=n_tok)


# ----------------------------------------------------------------- decode
def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=None
) -> Dict[str, jnp.ndarray]:
    """Ragged decode cache for all layers (attention KV and/or SSM state)."""
    dtype = dtype or cfg.compute_dtype
    hd = cfg.resolved_head_dim
    L = cfg.n_layers
    cache: Dict[str, jnp.ndarray] = {
        "lengths": jnp.zeros((batch,), jnp.int32)
    }
    if cfg.block in ("attn", "hybrid"):
        kv_len = max_len if cfg.sliding_window <= 0 else max_len
        cache["k"] = jnp.zeros(
            (L, batch, cfg.n_kv_heads_padded, kv_len, hd), dtype
        )
        cache["v"] = jnp.zeros_like(cache["k"])
    if cfg.block in ("ssm", "hybrid"):
        s = init_ssm_cache(cfg, batch, dtype)
        cache["ssm_conv"] = jnp.broadcast_to(
            s["conv"][None], (L,) + s["conv"].shape
        )
        cache["ssm_state"] = jnp.broadcast_to(
            s["state"][None], (L,) + s["state"].shape
        )
    if cfg.encdec:
        cache["enc_out"] = jnp.zeros((batch, cfg.encoder_len, cfg.d_model), dtype)
        # cross-attention K/V precomputed once per request (pure projections
        # of enc_out) instead of recomputed every decode step
        cache["cross_k"] = jnp.zeros(
            (L, batch, cfg.n_kv_heads_padded, cfg.encoder_len, hd), dtype
        )
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


def build_cross_cache(params: Dict[str, Any], cfg: ModelConfig, enc_out):
    """Per-layer cross-attn K/V from encoder output: (L, B, Hkv, T_enc, hd)."""
    stacked, _ = _split_layer_params(params)
    hd = cfg.resolved_head_dim
    hkv = cfg.n_kv_heads_padded
    dt = cfg.compute_dtype

    def body(_, pl):
        k = (enc_out @ pl["cross/wk"].astype(dt)).reshape(
            enc_out.shape[0], enc_out.shape[1], hkv, hd
        )
        v = (enc_out @ pl["cross/wv"].astype(dt)).reshape(
            enc_out.shape[0], enc_out.shape[1], hkv, hd
        )
        return (), (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    _, (ks, vs) = jax.lax.scan(body, (), stacked)
    return ks, vs


def decode_step(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jnp.ndarray,              # (B, 1) i32 newest token
    cache: Dict[str, jnp.ndarray],
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step for the whole batch; returns (logits (B, Vp), cache')."""
    x = embed_tokens(params, cfg, tokens)
    x = constrain(x, "batch", None, "embed")
    stacked, _ = _split_layer_params(params)
    wins = _layer_windows(cfg)
    lengths = cache["lengths"]
    enc_out = cache.get("enc_out")

    def body(h, xs):
        pl, win, kv = xs
        win = 0 if win is None else win
        aux_out = {}
        mix = jnp.zeros_like(h)
        hn = apply_norm(pl, "norm1", cfg, h)
        if cfg.block in ("attn", "hybrid"):
            a, nk, nv = apply_attn_decode(
                pl, "attn", cfg, hn, kv["k"], kv["v"], lengths, window=win
            )
            mix = mix + a
            aux_out["k"], aux_out["v"] = nk, nv
        if cfg.block in ("ssm", "hybrid"):
            sc = dict(conv=kv["ssm_conv"], state=kv["ssm_state"])
            sy, nc_ = apply_ssm_decode(pl, "ssm", cfg, hn, sc)
            mix = mix + sy
            aux_out["ssm_conv"], aux_out["ssm_state"] = nc_["conv"], nc_["state"]
        if cfg.block == "hybrid":
            mix = 0.5 * mix
        if cfg.parallel_block and cfg.moe is None and cfg.d_ff > 0:
            mix = mix + apply_mlp(pl, "mlp", cfg, hn)
            return h + mix, aux_out
        h = h + mix
        if cfg.encdec and enc_out is not None:
            hc = apply_norm(pl, "norm_cross", cfg, h)
            # cached cross K/V: pure gather + decode attention, no per-token
            # projection of the 1500-frame encoder output
            enc_lens = jnp.full((h.shape[0],), cfg.encoder_len, jnp.int32)
            c, _, _ = apply_attn_decode(
                pl, "cross", cfg, hc, kv["cross_k"], kv["cross_v"],
                enc_lens, use_rope=False, cross=True,
            )
            h = h + c
        if cfg.moe is not None:
            h2 = apply_norm(pl, "norm2", cfg, h)
            y, _ = apply_moe(pl, "moe", cfg, h2)
            h = h + y
        elif cfg.d_ff > 0 and cfg.block != "ssm":
            h2 = apply_norm(pl, "norm2", cfg, h)
            h = h + apply_mlp(pl, "mlp", cfg, h2)
        return h, aux_out

    kv_slices = {}
    for name in ("k", "v", "ssm_conv", "ssm_state", "cross_k", "cross_v"):
        if name in cache:
            kv_slices[name] = cache[name]
    x, new_kv = jax.lax.scan(
        body, x, (stacked, wins, kv_slices), unroll=get_unroll("layer")
    )
    x = apply_norm(params, "final_norm", cfg, x)
    logits = logits_fn(params, cfg, x[:, 0])
    new_cache = dict(cache)
    for name, v in new_kv.items():
        new_cache[name] = v
    new_cache["lengths"] = lengths + 1
    return logits, new_cache
