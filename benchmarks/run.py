"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks datasets
for CI-speed runs; default sizes match EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure list, e.g. fig10,fig11")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.common import CsvReport
    from benchmarks import (fig9_data_parallel, fig10_datastore,
                            fig11_ltfb_scaling, fig12_quality,
                            fig13_kindependent, fig14_serving)

    suites = {
        "fig9": fig9_data_parallel.run,
        "fig10": fig10_datastore.run,
        "fig11": fig11_ltfb_scaling.run,
        "fig12": fig12_quality.run,
        "fig13": fig13_kindependent.run,
        "fig14": fig14_serving.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}

    report = CsvReport()
    failed = []
    for name, fn in suites.items():
        try:
            fn(report, quick=args.quick)
        except Exception as e:
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            traceback.print_exc()
    report.dump()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
