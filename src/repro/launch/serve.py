"""Serving entry point: the epoch-synchronized (TVM) continuous-batching
engine over any architecture config.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --reduced \
      --requests 16 --slots 4 --max-new 24

``--no-reduced`` serves the architecture at its published widths.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from .. import configs
from ..models.model import init_model
from ..serving import EpochServer, Request
from .compile_cache import enable_compile_cache


def serve(cfg, *, requests: int = 16, slots: int = 4, max_new: int = 16,
          max_len: int = 128, seed: int = 0):
    """Serve ``requests`` random prompts (4..23 tokens) of ``cfg`` with
    random weights from ``seed``, to completion.

    Returns ``(server, params, done)``: the drained :class:`EpochServer`,
    the weights it served with, and the completed requests."""
    params, _ = init_model(cfg, jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    enc = None
    if cfg.encdec:
        import jax.numpy as jnp

        enc = jnp.asarray(
            rng.normal(size=(1, cfg.encoder_len, cfg.d_model)), jnp.float32
        )
    server = EpochServer(
        cfg, params, n_slots=slots, max_len=max_len, enc_frames=enc,
    )
    for _ in range(requests):
        plen = rng.randint(4, 24)
        server.submit(
            Request(
                prompt=rng.randint(3, cfg.vocab, size=plen).astype(np.int32),
                max_new_tokens=max_new,
            )
        )
    return server, params, server.run_to_completion()


def main(argv: Optional[list] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite_3_8b")
    ap.add_argument(
        "--reduced", action=argparse.BooleanOptionalAction, default=True,
        help="serve the small same-family config (--no-reduced: the "
        "published widths)",
    )
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_reduced if args.reduced else configs.get_config)(
        args.arch
    )
    t0 = time.time()
    server, _, done = serve(
        cfg, requests=args.requests, slots=args.slots,
        max_new=args.max_new, max_len=args.max_len, seed=args.seed,
    )
    dt = time.time() - t0
    n_tok = sum(len(r.output) for r in done)
    print(
        f"arch={cfg.name} served {len(done)} requests / {n_tok} tokens in "
        f"{server.epochs} epochs ({dt:.1f}s incl. init and compile, "
        f"slots={args.slots})"
    )
    for r in done[:3]:
        print(f"  rid={r.rid} len(prompt)={len(r.prompt)} out={r.output[:8]}…")


if __name__ == "__main__":
    main()
