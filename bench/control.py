#!/usr/bin/env python3
"""Readings for the limits of the check: sound runs and the control.

    python3 bench/control.py --workload bots_dc.closed --seconds 30 \
        --sound 11 12 13 --control 21 22 23

Runs the cell on the chip in one process (the wave template compiles
once): each ``--sound`` seed as the benchmark runs it, each ``--control``
seed with every answer replaced by the job kind's control, an answer that
breaks the configuration's guarantee of exact answers (``control`` in
``bench/kinds/*.py``).  Prints one JSON line per run with the numbers the
check compares.  The benchmark's own runs never run this.
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
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--sound", type=int, nargs="*", default=[])
    p.add_argument("--control", type=int, nargs="*", default=[])
    args = p.parse_args(argv)

    from bench import harness
    from repro.service.jobs import WaveTemplateCache

    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    devices = harness.tpu_devices(cell.chips)
    cache = WaveTemplateCache()
    runs = [(s, False) for s in args.sound] + [(s, True) for s in args.control]
    for seed, control in runs:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.monotonic(), devices=devices,
                               template_cache=cache, control=control)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "run": "control" if control else "sound",
            "correct": out["correct"], "attempted": out["attempted"],
            "checks": {k: v["value"] for k, v in out["checks"].items()},
            "jobs_per_s": out["metrics"].get("jobs_per_s", {}).get("value"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
