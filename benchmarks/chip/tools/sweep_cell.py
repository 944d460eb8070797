#!/usr/bin/env python3
"""``tools/sweep.py`` for a serving cell whose workload file names a
driver of its own (``sweep.py`` runs ``drivers/serve.py``): the same
rates, windows and output, with the driver the workload names.

  python3 benchmarks/chip/tools/sweep_cell.py \
      --workload dsv2lite-docqa-32k --rates 7,9,11 --seconds 30 --seeds 3

Each rate runs at the workload's own pre-roll and wait for first
tokens, as a benchmark run does.  One JSON line per rate and seed goes
to standard output and to ``chiprun_out/sweep_<workload>.jsonl`` when
that directory exists.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.traffic import sessions  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seeds", default="1")
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
    out_dir = ROOT / "chiprun_out"
    points = [(float(r), int(s)) for r in a.rates.split(",")
              for s in a.seeds.split(",")]
    for rate, seed in points:
        spec["traffic"]["rate_per_s"] = rate
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        run = harness.Run(args, cell, spec, config, time.perf_counter())
        reqs = sessions.generate(spec["traffic"], seed, a.seconds,
                                 config["vocab_size"])
        offered = sum(r.max_new for r in reqs if r.due >= 0) / a.seconds
        m = drv.measure(run)
        o = m.observations
        row = {"rate_per_s": rate, "seed": seed,
               "offered_tokens_per_s": offered,
               **m.metrics, "queued_at_close": o["queued_at_close"],
               "running_at_close": o["running_at_close"],
               "live_at_open": o["live_at_open"],
               "live_window_mean": o["live_window_mean"],
               "held_at_close": m.report,
               "failed": m.failed, "attempted": m.attempted,
               "setup_s": run.setup_s}
        print(json.dumps(row), flush=True)
        if out_dir.is_dir():
            with open(out_dir / f"sweep_{a.workload}.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
        m.check = None
        del m
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
