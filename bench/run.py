#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload bots_dc.closed --seed 7 --seconds 30 \
        --trace 0

Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``), then ``checks``, each number the
check compared beside its limit.  The same checks close standard error.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.tpu_devices(cell.chips)
    out = harness.run_cell(cell, args.seed % (1 << 63), args.seconds,
                           bool(args.trace), T_PROCESS, devices=devices)
    line = harness.result_line(out, devices)
    for k, v in line["checks"].items():
        harness.log(f"check {k}={v['value']} limit={v['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
