"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and record memory / cost / collective statistics.

This proves the distribution config is coherent without real hardware:
sharding mismatches, compile-time OOM, and unsupported collectives all fail
here.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]

Artifacts land in artifacts/dryrun/<cell>.json (incremental; safe to re-run
single cells).  benchmarks/roofline.py consumes them.
"""
import argparse
import functools
import json
import os
import pathlib
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..models.common import (
    finalize,
    logical_to_physical,
    sharding_ctx,
    unroll_ctx,
)
from ..models.model import decode_step, loss_fn, prefill
from ..optim import AdamW, OptState, zero1_pspec
from . import mesh as meshlib

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def art_dir(tag: str = "") -> pathlib.Path:
    return (
        ART_DIR if not tag
        else ART_DIR.parent / f"dryrun_{tag}"
    )

# --------------------------------------------------------------- HLO parse
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"\b(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _wire_bytes(op: str, size: int, g: int) -> float:
    """Per-chip bytes on the wire for a ring implementation of each op.

    ``size`` is the per-chip *result* buffer size from the HLO text (the
    compiled module is the per-device program)."""
    g = max(g, 2)
    if op == "all-reduce":
        return 2.0 * size * (g - 1) / g
    if op == "all-gather":
        return size * (g - 1) / g          # result = full gathered buffer
    if op == "reduce-scatter":
        return size * (g - 1)              # result = 1/g of the operand
    if op == "all-to-all":
        return size * (g - 1) / g
    return float(size)                     # collective-permute


def collective_stats(hlo_text: str, n_devices: int = 512) -> Dict[str, Any]:
    """Per-chip collective statistics parsed from the partitioned module.

    For every all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute we take the result-buffer size and the replica-group
    size and derive ring wire bytes (see _wire_bytes).
    """
    stats: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        # result type(s): between '=' and the op name
        eq = line.find("=")
        result_part = line[eq + 1 : m.start()] if eq >= 0 else ""
        size = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(result_part)
        )
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            g = len(gb.group(1).split(",")) if gb else n_devices
        s = stats.setdefault(op, {"count": 0, "result_bytes": 0, "wire_bytes": 0.0})
        s["count"] += 1
        s["result_bytes"] += size
        s["wire_bytes"] += _wire_bytes(op, size, g)
    total = sum(s["wire_bytes"] for s in stats.values())
    return {"ops": stats, "total_bytes_per_chip": total}


# ------------------------------------------------------------ input specs
def input_specs(
    cfg, mesh, shape: configs.ShapeSpec
) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell —
    weak-type-correct, sharded, no device allocation."""
    B, S = shape.global_batch, shape.seq_len
    bspec = meshlib.batch_pspec(mesh, B)
    b_ax = bspec[0] if len(bspec) else None

    def tok(shp):
        return jax.ShapeDtypeStruct(
            shp, jnp.int32, sharding=NamedSharding(mesh, P(b_ax, None))
        )

    if shape.kind == "train":
        batch = {"tokens": tok((B, S)), "labels": tok((B, S))}
        if cfg.encdec:
            batch["enc_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_len, cfg.d_model), jnp.float32,
                sharding=NamedSharding(mesh, P(b_ax, None, None)),
            )
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": tok((B, S))}
        if cfg.encdec:
            batch["enc_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_len, cfg.d_model), jnp.float32,
                sharding=NamedSharding(mesh, P(b_ax, None, None)),
            )
        return batch
    # decode: one new token against a cache of seq_len
    return {
        "tokens": tok((B, 1)),
        "cache": meshlib.cache_specs(cfg, mesh, B, S),
    }


# ----------------------------------------------------- loop trip inventory
def loop_table(cfg, shape: configs.ShapeSpec):
    """(name, trips, parent) of every while loop the lowered step contains —
    used to correct XLA's body-counted-once cost analysis (see unroll_ctx)."""
    S = shape.seq_len
    loops = [("layer", cfg.n_layers, None)]
    has_attn = cfg.block in ("attn", "hybrid")
    if shape.kind in ("train", "prefill"):
        if has_attn and S > 1024:
            loops.append(("kv_self", -(-S // 512), "layer"))
        if cfg.block in ("ssm", "hybrid"):
            loops.append(("ssd", -(-S // 128), "layer"))
        if cfg.encdec:
            loops.append(("enc", cfg.n_encoder_layers, None))
            if cfg.encoder_len > 1024:
                kvt = -(-cfg.encoder_len // 512)
                loops.append(("kv_enc", kvt, "enc"))
                loops.append(("kv_cross", kvt, "layer"))
    if shape.kind == "train":
        loops.append(("chunk", -(-S // 512), None))
    return loops


# -------------------------------------------------------------- cell build
def build_cell(
    arch: str, shape_name: str, multi_pod: bool,
    overrides: Optional[Dict[str, str]] = None,
):
    import dataclasses as _dc

    import jax.numpy as _jnp

    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    cfg = finalize(configs.get_config(arch), mesh.shape["model"])
    ov = dict(overrides or {})
    master_weights = bool(int(ov.pop("master_weights", "0")))
    seq_par = bool(int(ov.pop("sequence_parallel", "0")))
    replicate_ffn = bool(int(ov.pop("replicate_ffn", "0")))
    if "param_dtype" in ov:
        ov["param_dtype"] = dict(bf16=_jnp.bfloat16, f32=_jnp.float32)[
            ov["param_dtype"]
        ]
    if "dispatch" in ov and cfg.moe is not None:
        cfg = _dc.replace(
            cfg, moe=_dc.replace(cfg.moe, dispatch=ov.pop("dispatch"))
        )
    if ov:
        cfg = _dc.replace(cfg, **ov)
    shape = configs.SHAPES[shape_name]
    skip = configs.skip_reason(cfg, shape)
    if skip:
        return None, None, None, skip
    rules = meshlib.rules_for_mesh(mesh, sequence_parallel=seq_par)
    if replicate_ffn:
        # small models over-TP'd: replicate the FFN/SSM weights (DP-only for
        # the body, vocab stays sharded) -> kills per-layer TP all-reduces
        rules = rules.replace(
            mlp=None, ssm_inner=None, heads=None, kv_heads=None
        )
    specs = input_specs(cfg, mesh, shape)
    pspecs, _ = meshlib.param_shardings(cfg, mesh, rules)

    if shape.kind == "train":
        opt = AdamW(lr=1e-4, master_weights=master_weights)
        dax = meshlib.data_axes(mesh)
        dsz = meshlib.data_size(mesh)
        mom = {
            k: jax.ShapeDtypeStruct(
                v.shape, jnp.float32,
                sharding=NamedSharding(
                    mesh,
                    zero1_pspec(v.sharding.spec, v.shape, dax, dsz),
                ),
            )
            for k, v in pspecs.items()
        }
        opt_specs = OptState(
            m=mom,
            v=dict(mom),
            step=jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=NamedSharding(mesh, P())
            ),
            master=dict(mom) if master_weights else {},
        )

        def make_fn(unroll):
            def train_step(params, opt_state, batch):
                with unroll_ctx(**unroll), sharding_ctx(mesh, rules):
                    (loss, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params, cfg, batch)
                    new_p, new_s, om = opt.update(params, grads, opt_state)
                return new_p, new_s, dict(loss=loss, **om)

            return jax.jit(train_step, donate_argnums=(0, 1))

        args = (pspecs, opt_specs, specs)
    elif shape.kind == "prefill":
        def make_fn(unroll):
            def prefill_step(params, batch):
                with unroll_ctx(**unroll), sharding_ctx(mesh, rules):
                    return prefill(
                        params, cfg, batch["tokens"],
                        enc_frames=batch.get("enc_frames"),
                    )

            return jax.jit(prefill_step)

        args = (pspecs, specs)
    else:
        def make_fn(unroll):
            def serve_step(params, tokens, cache):
                with unroll_ctx(**unroll), sharding_ctx(mesh, rules):
                    return decode_step(params, cfg, tokens, cache)

            return jax.jit(serve_step, donate_argnums=(2,))

        args = (pspecs, specs["tokens"], specs["cache"])
    return make_fn, args, (mesh, cfg, shape), None


def _measure(make_fn, args, unroll: Dict[str, int]):
    """Lower+compile under an unroll assignment; return raw stats."""
    lowered = make_fn(unroll).lower(*args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    colls = collective_stats(compiled.as_text())
    return dict(
        flops=float(cost.get("flops", 0.0)),
        bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=float(colls["total_bytes_per_chip"]),
        coll_ops=colls["ops"],
        memory=mem,
    )


def calibrated_stats(make_fn, args, loops):
    """Trip-count-corrected per-device flops/bytes/collective-bytes.

    XLA's cost_analysis counts each while-loop body once.  For every loop we
    lower twice (unroll 1 vs 2) and difference, then scale each loop's
    per-trip cost by its effective trip count (product up the nesting tree):
      corrected = base + sum_i (eff_trips_i - 1) * per_trip_i
    """
    base = _measure(make_fn, args, {})
    D = {}
    for name, trips, parent in loops:
        if trips <= 1:
            D[name] = dict(flops=0.0, bytes=0.0, coll_bytes=0.0)
            continue
        m = _measure(make_fn, args, {name: 2})
        D[name] = {
            k: max(0.0, m[k] - base[k])
            for k in ("flops", "bytes", "coll_bytes")
        }
    parents = {name: parent for name, _, parent in loops}
    trips_of = {name: t for name, t, _ in loops}

    def eff(name):
        t = trips_of[name]
        p = parents[name]
        return t * (eff(p) if p else 1)

    corrected = {k: base[k] for k in ("flops", "bytes", "coll_bytes")}
    per_trip = {}
    for name, trips, parent in loops:
        children = [n for n, p in parents.items() if p == name]
        pt = {
            k: max(0.0, D[name][k] - sum(D[c][k] for c in children))
            for k in ("flops", "bytes", "coll_bytes")
        }
        per_trip[name] = pt
        for k in corrected:
            corrected[k] += (eff(name) - 1) * pt[k]
    return base, corrected, per_trip, {
        n: dict(trips=trips_of[n], eff=eff(n), parent=parents[n])
        for n in trips_of
    }


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, save: bool = True,
    tag: str = "", overrides: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    cell_id = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    rec: Dict[str, Any] = {
        "cell": cell_id, "arch": arch, "shape": shape_name,
        "multi_pod": multi_pod,
    }
    t0 = time.time()
    try:
        make_fn, args, ctx, skip = build_cell(
            arch, shape_name, multi_pod, overrides
        )
        if skip:
            rec["status"] = "skipped"
            rec["skip_reason"] = skip
        else:
            mesh, cfg, shape = ctx
            loops = loop_table(cfg, shape)
            base, corrected, per_trip, trips = calibrated_stats(
                make_fn, args, loops
            )
            mem = base["memory"]
            rec.update(
                status="ok",
                n_devices=int(np.prod(list(mesh.shape.values()))),
                mesh={k: int(v) for k, v in mesh.shape.items()},
                flops_per_device=corrected["flops"],
                bytes_per_device=corrected["bytes"],
                coll_bytes_per_device=corrected["coll_bytes"],
                uncorrected=dict(
                    flops=base["flops"], bytes=base["bytes"],
                    coll_bytes=base["coll_bytes"],
                ),
                loop_calibration=dict(per_trip=per_trip, trips=trips),
                collectives_base=base["coll_ops"],
                memory=dict(
                    argument_bytes=int(mem.argument_size_in_bytes),
                    output_bytes=int(mem.output_size_in_bytes),
                    temp_bytes=int(mem.temp_size_in_bytes),
                    alias_bytes=int(mem.alias_size_in_bytes),
                ),
                n_params_logical=int(cfg.n_params()),
                n_params_active=int(cfg.active_params()),
                kind=shape.kind,
                seq_len=shape.seq_len,
                global_batch=shape.global_batch,
            )
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["elapsed_s"] = round(time.time() - t0, 1)
    if save:
        d = art_dir(tag)
        d.mkdir(parents=True, exist_ok=True)
        (d / f"{cell_id}.json").write_text(json.dumps(rec, indent=1))
    status = rec["status"]
    extra = rec.get("skip_reason") or rec.get("error", "")
    print(f"[dryrun] {cell_id}: {status} ({rec['elapsed_s']}s) {extra}",
          flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(configs.SHAPES))
    ap.add_argument(
        "--multi-pod", default="both", choices=["0", "1", "both"]
    )
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--skip-existing", action="store_true",
        help="skip cells whose artifact already says status=ok",
    )
    ap.add_argument(
        "--tag", default="",
        help="write artifacts to artifacts/dryrun_<tag>/ (perf variants)",
    )
    ap.add_argument(
        "--set", action="append", default=[], metavar="KEY=VAL",
        help="config overrides, e.g. --set remat=dots --set param_dtype=bf16",
    )
    args = ap.parse_args()
    # 512 host devices stand in for the production meshes; set before the
    # first jax call initializes a backend, never at import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    pods = {"0": [False], "1": [True], "both": [False, True]}[args.multi_pod]
    archs = (
        configs.ARCH_IDS
        if args.all or not args.arch
        else [configs.normalize(args.arch)]
    )
    shapes = list(configs.SHAPES) if args.all or not args.shape else [args.shape]

    failed = 0
    for mp in pods:
        for arch in archs:
            for shp in shapes:
                cell_id = (
                    f"{arch}__{shp}__{'pod2' if mp else 'pod1'}"
                )
                if args.skip_existing:
                    f = art_dir(args.tag) / f"{cell_id}.json"
                    if f.exists():
                        old = json.loads(f.read_text())
                        if old.get("status") in ("ok", "skipped"):
                            print(f"[dryrun] {cell_id}: cached", flush=True)
                            continue
                overrides = dict(kv.split("=", 1) for kv in args.set)
                rec = run_cell(
                    arch, shp, mp, tag=args.tag, overrides=overrides
                )
                failed += rec["status"] == "failed"
    if failed:
        raise SystemExit(f"{failed} cells failed")


if __name__ == "__main__":
    main()
