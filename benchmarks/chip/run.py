#!/usr/bin/env python3
"""Chip benchmark: one cell, one seed, one measured window.

  python3 benchmarks/chip/run.py --workload qwen3-chat-shared --seed 7 \
      --seconds 30 --trace 0

Reads the cell from ``BENCHMARK.json``; its traffic and set-up from
``benchmarks/chip/workloads/<cell>.json``; its sizes from the
configuration file the manifest names; its driver from
``benchmarks/chip/drivers/<driver>.py``; and each per-layer metric from
``benchmarks/chip/metrics/<metric>.py``.  Set-up (weights and data from
``--seed``, warm-up of every shape the traffic reaches) is timed as
``setup_s``; then the driver measures for ``--seconds``.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the profiler's trace of
part of the window, from the program's counters and from the harness's
clock.  After the window the driver compares what the timed path
produced with a plain reference; the numbers compared are printed with
their limits as the last lines on standard error and under ``checks``
in the result, which is the last line on standard output.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits nonzero.  JAX's compilation cache lives in
``benchmarks/chip/.cache/jax`` inside the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_end_to_end(bench: dict, workload: str) -> list:
    """The end-to-end metrics the manifest gives this cell."""
    return [m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer(run, bench: dict, measured, trace_obj) -> dict:
    """Every per-layer metric this cell lists, from its reader; a reader
    that finds nothing returns None and the metric is left out."""
    from benchmarks.chip import flops

    e2e = set(cell_end_to_end(bench, run.workload))
    obs = dict(measured.observations)
    obs.update(config=run.config, spec=run.spec, chips=run.chips,
               peaks=flops.peaks_for(run.devices[0].device_kind),
               trace=trace_obj,
               trace_devices=[d.id for d in run.devices])
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and run.workload not in cells:
            continue
        if cells is None and m["moves"] not in e2e:
            continue
        reader = harness.load_file(CHIP / "metrics" / f"{m['name']}.py",
                                   f"bench_metric_{len(out)}")
        value = reader.read(obs)
        if value is None:
            harness.log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, require_tpu: bool = True, overrides=None):
    """Set up, measure and check one cell; returns the result object, or
    None when the devices do not fit the cell.  ``overrides`` (tests
    only) updates the configuration and workload read from the files."""
    if not (ROOT / "src" / "repro").is_dir():
        harness.log(f"no program beside the benchmark (looked for "
                    f"{ROOT / 'src' / 'repro'})")
        return None
    harness.ensure_env()
    bench = harness.load_manifest(ROOT)
    cell, spec, config = harness.cell_files(bench, args.workload, ROOT)
    if overrides:
        config.update(overrides.get("config", {}))
        for k, v in overrides.get("spec", {}).items():
            if isinstance(v, dict):
                spec[k] = {**spec.get(k, {}), **v}
            else:
                spec[k] = v

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        harness.log(f"needs a TPU; JAX found {devs[0].platform}")
        return None
    if len(devs) < int(cell["chips"]):
        harness.log(f"cell {args.workload} needs {cell['chips']} chips; "
                    f"JAX sees {len(devs)}")
        return None
    run = harness.Run(args, cell, spec, config, T_PROCESS)
    harness.log(f"{args.workload} seed={args.seed} seconds={args.seconds} "
                f"trace={args.trace} device={devs[0].device_kind} "
                f"x{len(devs)} jax={jax.__version__}")
    driver = harness.load_file(CHIP / "drivers" / f"{spec['driver']}.py",
                               f"bench_driver_{spec['driver']}")
    measured = driver.measure(run)
    peak = run.memory_peak_bytes()
    harness.log(f"memory_peak_bytes={peak}")
    checks = measured.check() if measured.check is not None else []
    measured.check = None
    gc.collect()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    breakdown = None
    if run.trace_on:
        import shutil

        from benchmarks.chip import trace as trace_mod

        tr = trace_mod.load(str(run.trace_dir))
        summary = trace_mod.summarize(tr, [d.id for d in run.devices],
                                      prefer=harness.SPANS)
        device.update(busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        metrics = per_layer(run, bench, measured, tr)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        values = {"setup_s": run.setup_s, **measured.metrics}
        metrics = {k: {"value": float(values[k]), "unit": units[k]}
                   for k in cell_end_to_end(bench, args.workload)}
    correct = bool(checks) and all(c.ok for c in checks)
    return harness.result(correct, measured.attempted, measured.failed,
                          metrics, device, checks, breakdown,
                          measured.report)


def main(argv=None) -> int:
    result = run_cell(parse(argv))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        harness.log(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
                    f"{ok}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
