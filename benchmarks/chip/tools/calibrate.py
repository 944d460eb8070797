#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers on many
seeds, and the control's and the planted faults' on a few.

  python3 benchmarks/chip/tools/calibrate.py --workload qwen3-chat-shared \
      --seeds 1,2,3 --control-seeds 1,2 --seconds 20
  python3 benchmarks/chip/tools/calibrate.py --workload qwen3-chat-shared \
      --seeds 4,5,6 --fault temperature_ignored --seconds 20

Each seed runs the cell's driver as a benchmark run does (set-up, a
window at the cell's load, the comparison with the reference); on the
control seeds the comparison also reads the control (the reference in
the precision below the configuration's, put in the program's place)
and, for training, the planted faults.  ``--fault`` plants one of
``tools/faults.py``'s sampler faults in the serving program for every
seed.  One JSON line per seed goes to
standard output and to ``chiprun_out/calibrate_<workload>.jsonl`` when
that directory exists.  The lower reading of a number is the largest
the program gives; its upper reading the smallest a control or fault
gives.
"""
from __future__ import annotations

import time

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--fault", default="")
    a = ap.parse_args()
    harness.ensure_env()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        harness.log("needs a TPU")
        return 1
    bench = harness.load_manifest()
    cell, spec, config = harness.cell_files(bench, a.workload)
    drv = harness.load_file(harness.CHIP / "drivers" / f"{spec['driver']}.py",
                            "drv")
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    if a.fault:
        from benchmarks.chip.tools import faults

        faults.plant(a.fault)
    out_dir = ROOT / "chiprun_out"
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        run = harness.Run(args, cell, spec, config, time.perf_counter())
        m = drv.measure(run)
        peak = run.memory_peak_bytes()
        checks = m.check(control=seed in controls)
        row = {"seed": seed, "fault": a.fault or None,
               "metrics": m.metrics, "setup_s": run.setup_s,
               "memory_peak_bytes": peak,
               "readings": {c.name: c.value for c in checks}}
        print(json.dumps(row), flush=True)
        if out_dir.is_dir():
            with open(out_dir / f"calibrate_{a.workload}.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
        m.check = None
        del m, checks
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
