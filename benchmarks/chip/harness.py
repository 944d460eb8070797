"""What every driver shares: the run's context, compile counting,
percentiles, the manifest and the result line.

Nothing here imports JAX at module level: ``run.py`` fixes the
compilation cache and the log directory in the environment first.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

from benchmarks.chip import trace

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
CACHE = CHIP / ".cache"

# annotation names the harness wraps around each call into a layer; the
# trace reduction labels idle gaps by them
SPANS = ("ltfb.train_round", "ltfb.tournament", "serve.step", "gen.submit",
         "gen.wait")
# seconds at the start of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 10.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default method), without
    numpy so that readers stay plain."""
    v = sorted(xs)
    if not v:
        raise ValueError("percentile of no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def device_key(seed: int):
    """A JAX PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def load_file(path: Path, name: str):
    """Import a module from a file (drivers and readers are found by
    the names ``BENCHMARK.json`` gives them, which may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_files(bench: dict, workload: str, root: Path = ROOT):
    """The cell's manifest entry, its workload file (driver and set-up,
    with the traffic file it names under ``traffic``) and its
    configuration file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    spec = json.loads((CHIP / "workloads" / f"{workload}.json").read_text())
    spec["traffic"] = json.loads(
        (CHIP / "traffic" / f"{cell['traffic']}.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    return cell, spec, config


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit is counted as a compile of its load time)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits)


@dataclass
class Check:
    """One number compared with its limit; the run is correct when every
    value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Measured:
    """What a driver's measured window gives the harness."""
    metrics: Dict[str, float]
    attempted: int
    failed: int
    observations: Dict[str, Any] = field(default_factory=dict)
    # drops the program's state and runs the comparison with the
    # reference; called after the peak memory has been read
    check: Optional[Callable[[], List[Check]]] = None
    # what the server holds in steady state (bytes and pages in use),
    # reported beside the peak
    report: Optional[Dict[str, Any]] = None


class Run:
    """One invocation: arguments, manifest entries, devices, clocks."""

    def __init__(self, args, cell: dict, spec: dict, config: dict,
                 t_process: float):
        import jax

        self.args = args
        self.workload = args.workload
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace_on = bool(args.trace)
        self.cell, self.spec, self.config = cell, spec, config
        self.chips = int(cell["chips"])
        self.devices = jax.devices()[:self.chips]
        self.t_process = t_process
        self.t_window: Optional[float] = None
        self.meter = CompileMeter()
        self.window_compiles = None
        self.trace_dir = CACHE / "trace" / self.workload
        self._tracing = False
        self._window_span = None

    # -- clocks -------------------------------------------------------------
    def window_opens(self, at: Optional[float] = None) -> float:
        """Mark the end of set-up (now, or at the ``perf_counter`` time
        ``at`` that a driver scheduled); returns the window's start."""
        self.t_window = time.perf_counter() if at is None else at
        self._snap = self.meter.snapshot()
        log(f"set-up {self.t_window - self.t_process:.2f} s, "
            f"{self.meter.compiles} compiles ({self.meter.seconds:.2f} s, "
            f"{self.meter.cache_hits} from the persistent cache)")
        return self.t_window

    def window_closes(self) -> None:
        s, c, h = self._snap
        self.window_compiles = self.meter.compiles - c
        if self.window_compiles:
            log(f"WARNING: {self.window_compiles} compiles inside the "
                f"measured window ({self.meter.seconds - s:.2f} s)")

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process

    # -- tracing ------------------------------------------------------------
    def span(self, name: str):
        """A named host span in the profiler's trace (cheap when no trace
        is being recorded)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_start(self) -> None:
        """Start the profiler (when the run asked for a trace) and open
        the ``bench.window`` span that bounds what the readers see: the
        first ``trace_seconds`` of the window (the workload file's, else
        ``TRACE_SECONDS``), so that the trace stays small enough to keep
        every device event and to be read within the run's time."""
        if not self.trace_on:
            return
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._tracing = True
        self._window_span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        self._window_span.__enter__()
        self._trace_until = time.perf_counter() + float(
            self.spec.get("trace_seconds", TRACE_SECONDS))

    def traced(self) -> bool:
        """True while the traced part of the window is open; closes it
        once its time has passed.  Drivers ask between calls into the
        program, so the part ends on a whole call."""
        if self._window_span is not None \
                and time.perf_counter() >= self._trace_until:
            self.trace_window_ends()
        return self._window_span is not None

    def trace_window_ends(self) -> None:
        """Close the ``bench.window`` span (the trace may run on)."""
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None

    def trace_stop(self) -> None:
        """Stop the profiler and write the trace; called once the work
        the window waits for is done, so writing it delays nothing that
        is measured."""
        self.trace_window_ends()
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest chip (0 where the backend
        keeps no such count, as the CPU in the tests)."""
        return self._memory("peak_bytes_in_use")

    def memory_in_use_bytes(self) -> int:
        """Bytes in use now on the fullest chip (0 as above)."""
        return self._memory("bytes_in_use")

    def _memory(self, key: str) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(int(s.get(key, 0)) for s in stats)


def result(correct: bool, attempted: int, failed: int,
           metrics: Dict[str, dict], device: dict, checks: List[Check],
           breakdown: Optional[dict],
           held: Optional[dict] = None) -> Dict[str, Any]:
    """The result object, with the numbers compared last."""
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed),
                           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if held is not None:
        out["held_at_close"] = held
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def ensure_env() -> None:
    """Cache and log locations inside the checkout, fixed paths, set
    before JAX is imported."""
    CACHE.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
