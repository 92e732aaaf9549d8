"""Dry-run machinery tests: HLO collective parsing, loop calibration math,
and a small-mesh end-to-end lower+compile in a subprocess (the production
512-device sweep runs via `python -m repro.launch.dryrun --all`)."""
import pathlib
import subprocess
import sys

import pytest

from conftest import multidevice_skip

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def test_collective_stats_parser():
    sys.path.insert(0, SRC)
    from repro.launch.dryrun import collective_stats

    hlo = """
  %all-reduce.5 = f32[2048]{0} all-reduce(%x), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  %ag = bf16[16,512]{1,0} all-gather(%y), replica_groups=[4,2]<=[8], dimensions={1}
  %rs = f32[128]{0} reduce-scatter(%z), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[64]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %done = f32[2048]{0} all-reduce-done(%all-reduce.5)
"""
    s = collective_stats(hlo)
    ops = s["ops"]
    assert ops["all-reduce"]["count"] == 1  # -done not double counted
    assert ops["all-reduce"]["result_bytes"] == 2048 * 4
    # ring wire bytes: 2*S*(g-1)/g with g=4
    assert abs(ops["all-reduce"]["wire_bytes"] - 2 * 8192 * 3 / 4) < 1
    assert ops["all-gather"]["result_bytes"] == 16 * 512 * 2
    assert ops["reduce-scatter"]["wire_bytes"] == 128 * 4 * 3  # S*(g-1), g=4
    assert ops["collective-permute"]["wire_bytes"] == 64 * 4
    assert s["total_bytes_per_chip"] > 0


def test_loop_calibration_math():
    """corrected = base + sum (eff_trips-1) * per_trip with nesting."""
    sys.path.insert(0, SRC)
    from repro.launch.dryrun import calibrated_stats

    # synthetic program: outer loop L=4 trips, inner loop (child) 3 trips
    # true flops = O + 4*layer_base + 4*3*inner_body
    O, layer_base, inner = 100.0, 10.0, 2.0
    loops = [("layer", 4, None), ("ssd", 3, "layer")]

    def make_fn(unroll):
        class FakeLowered:
            def compile(self):
                return self

            def lower(self, *a):
                return self

            def memory_analysis(self):
                class M:
                    argument_size_in_bytes = 0
                    output_size_in_bytes = 0
                    temp_size_in_bytes = 0
                    alias_size_in_bytes = 0
                return M()

            def cost_analysis(self):
                lu = unroll.get("layer", 1)
                su = unroll.get("ssd", 1)
                # each unrolled copy of the layer body contains su ssd bodies
                f = O + lu * (layer_base + su * inner)
                return {"flops": f, "bytes accessed": f}

            def as_text(self):
                return ""

        return FakeLowered()

    base, corrected, per_trip, trips = calibrated_stats(make_fn, (), loops)
    want = O + 4 * layer_base + 12 * inner
    assert abs(corrected["flops"] - want) < 1e-6, (corrected["flops"], want)
    assert trips["ssd"]["eff"] == 12


_SKIP, _REASON = multidevice_skip(required=8)


@pytest.mark.skipif(_SKIP, reason=_REASON)
def test_small_mesh_cell_lowers():
    """End-to-end: a reduced config lowers+compiles on a 2x4 mesh with the
    same code paths as the production dry-run (subprocess, 8 devices)."""
    script = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch import mesh as meshlib
from repro.models.common import finalize, sharding_ctx
from repro.models.model import loss_fn
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = finalize(configs.get_reduced("granite_moe_1b_a400m"), 4)
rules = meshlib.rules_for_mesh(mesh)
pspecs, _ = meshlib.param_shardings(cfg, mesh, rules)
B, S = 8, 64
bsh = NamedSharding(mesh, P("data", None))
batch = {
  "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh),
  "labels": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh),
}
def step(params, batch):
    with sharding_ctx(mesh, rules):
        return loss_fn(params, cfg, batch)[0]
compiled = jax.jit(step).lower(pspecs, batch).compile()
assert compiled.cost_analysis()["flops"] > 0
print("LOWER_OK", compiled.cost_analysis()["flops"])
"""
    env = {
        "PYTHONPATH": SRC,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PATH": "/usr/bin:/bin",
        "HOME": "/tmp",
    }
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=480, env=env,
    )
    assert r.returncode == 0, r.stderr
    assert "LOWER_OK" in r.stdout
