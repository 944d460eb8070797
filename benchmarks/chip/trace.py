"""Reduce a JAX profiler trace to device busy time, per-operation
device time and idle gaps attributed to what the host was doing.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
:func:`load` reads it with ``jax.profiler.ProfileData`` alone.  Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per HLO operation (the event name is the instruction's text,
``%name.N = ...``) and their ``XLA Modules`` line one per program run.
The host plane ``/host:CPU`` holds ``TraceAnnotation`` spans on the
line of the thread that opened them (on the ``python`` line when the
Python tracer is on).  Host and device timestamps share one clock to
about a millisecond, which is finer than the gaps attributed here.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LINE = "python"
WINDOW_SPAN = "bench.window"
MIN_LABELLED_GAP_NS = 20_000

Interval = Tuple[float, float, str]       # (start_ns, end_ns, name)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


_OP_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


def op_name(event_name: str) -> str:
    """Short operation name: ``%paged_attention.1 = bf16[...] ...`` ->
    ``paged_attention``; names without an HLO body pass through."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name.split("(")[0]


def module_name(event_name: str) -> str:
    """``jit__step_tables_fn(5237686421168062044)`` -> ``jit__step_tables_fn``."""
    return event_name.split("(")[0]


@dataclass
class Trace:
    ops: Dict[int, List[Interval]] = field(default_factory=dict)
    modules: Dict[int, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Tuple[float, float]:
        """Bounds of the harness's ``bench.window`` span (the traced part
        of the measured window); the whole trace when it is absent."""
        spans = [(s, e) for s, e, n in self.host if n == WINDOW_SPAN]
        if spans:
            return spans[0]
        evs = [iv for d in self.ops.values() for iv in d] + self.host
        return (min(s for s, _, _ in evs), max(e for _, e, _ in evs))


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            dev = int(name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                evs.sort()
                (tr.ops if line.name == OPS_LINE else tr.modules)[dev] = evs
        elif name == HOST_PLANE:
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
                     for line in plane.lines}
            # the harness's own thread is the line that holds its window
            # span (with Python tracing off, annotations land on the
            # thread's line, named after the thread); else the Python
            # tracer's line
            own = [evs for evs in lines.values()
                   if any(n == WINDOW_SPAN for _, _, n in evs)]
            for evs in own or [lines.get(HOST_LINE, [])]:
                tr.host.extend(evs)
    tr.host.sort()
    return tr


def load(path: str) -> Trace:
    """Read a trace file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile(ProfileData.from_file(path))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def merged(intervals: Iterable[Interval], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Union of the intervals, clipped to [lo, hi], in order."""
    out: List[Tuple[float, float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_seconds(tr: Trace, dev: int, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some operation ran on ``dev``."""
    return sum(e - s for s, e in merged(tr.ops.get(dev, ()), lo, hi)) / 1e9


def self_times(events: Sequence[Interval], lo: float, hi: float
               ) -> List[Tuple[float, float, str]]:
    """Each event clipped to [lo, hi] with its self time: a ``while`` or
    call on the ``XLA Ops`` line encloses the operations of its body,
    whose time is its children's, not its own.  Returns
    (start_ns, self_seconds, name)."""
    out, stack = [], []          # stack of [end, index into out]
    for s, e, name in sorted(events, key=lambda iv: (iv[0], -iv[1])):
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2:
            continue
        while stack and stack[-1][0] <= s2:
            stack.pop()
        dur = (e2 - s2) / 1e9
        if stack and e2 <= stack[-1][0]:
            parent = out[stack[-1][1]]
            out[stack[-1][1]] = (parent[0], parent[1] - dur, parent[2])
        out.append((s2, dur, name))
        stack.append([e2, len(out) - 1])
    return out


def op_seconds(tr: Trace, dev: int, lo: float, hi: float,
               match=None) -> Dict[str, float]:
    """Device self seconds per short operation name on ``dev`` in
    [lo, hi]; ``match(short_name, full_text)`` filters."""
    out: Dict[str, float] = defaultdict(float)
    for _, dur, name in self_times(tr.ops.get(dev, ()), lo, hi):
        short = op_name(name)
        if match is None or match(short, name):
            out[short] += dur
    return dict(out)


def ops_in_module(tr: Trace, dev: int, lo: float, hi: float, module: str,
                  match) -> float:
    """Device self seconds of the operations ``match(short, text)``
    picks inside runs of the program ``module`` on ``dev`` in [lo, hi]
    (an operation belongs to the run it starts in)."""
    runs = merged([iv for iv in tr.modules.get(dev, ())
                   if module_name(iv[2]) == module], lo, hi)
    starts = [s for s, _ in runs]
    total = 0.0
    for start, dur, name in self_times(tr.ops.get(dev, ()), lo, hi):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][1] and match(op_name(name), name):
            total += dur
    return total


def module_seconds(tr: Trace, dev: int, lo: float, hi: float,
                   match=None) -> Dict[str, float]:
    """Device seconds per program (``XLA Modules``) on ``dev``."""
    out: Dict[str, float] = defaultdict(float)
    for s, e, name in tr.modules.get(dev, ()):
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2:
            continue
        short = module_name(name)
        if match is None or match(short):
            out[short] += (e2 - s2) / 1e9
    return dict(out)


def idle_percent(tr: Trace, devices: Sequence[int]) -> Optional[float]:
    """Share of the traced window in which no operation ran, averaged
    over ``devices`` (those the trace holds); None when it holds none."""
    lo, hi = tr.window()
    devs = [d for d in devices if d in tr.ops]
    if not devs or hi <= lo:
        return None
    busy = sum(busy_seconds(tr, d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))


def gaps(tr: Trace, dev: int, lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of ``dev`` inside [lo, hi]."""
    out, cur = [], lo
    for s, e in merged(tr.ops.get(dev, ()), lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


class _HostIndex:
    """Host spans sorted by start with a running maximum of their ends,
    so the spans covering an instant are found without a full scan."""

    def __init__(self, tr: Trace):
        self.spans = [iv for iv in tr.host
                      if iv[2] != WINDOW_SPAN and not iv[2].startswith("$")]
        self.starts = [s for s, _, _ in self.spans]
        self.reach, top = [], float("-inf")
        for _, e, _ in self.spans:
            top = max(top, e)
            self.reach.append(top)

    def covering(self, t: float) -> List[Interval]:
        out = []
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.reach[j] >= t:
            if self.spans[j][1] >= t:
                out.append(self.spans[j])
            j -= 1
        return out


def host_label(tr: Trace, t: float, prefer: Sequence[str] = (),
               index: Optional[_HostIndex] = None) -> str:
    """What the host was doing at time ``t``: the innermost harness span
    (a name in ``prefer``) covering it, joined to the innermost other
    span when there is one; ``idle`` when nothing covers it."""
    covering = (index or _HostIndex(tr)).covering(t)
    if not covering:
        return "idle"
    inner = max(covering, key=lambda iv: iv[0])[2]
    named = [iv for iv in covering if iv[2] in prefer]
    if not named:
        return inner
    outer = max(named, key=lambda iv: iv[0])[2]
    return outer if outer == inner else f"{outer}>{inner}"


def summarize(tr: Trace, devices: Optional[Sequence[int]] = None,
              prefer: Sequence[str] = (), top: int = 10) -> dict:
    """Busy and window seconds (mean over ``devices``), the operations
    that took most device time and the idle time grouped by what the
    host was doing, each list at most ``top`` long."""
    lo, hi = tr.window()
    devs = list(devices) if devices is not None else tr.devices
    if not devs:
        raise ValueError("trace holds no device plane")
    busy = [busy_seconds(tr, d, lo, hi) for d in devs]
    ops: Dict[str, float] = defaultdict(float)
    for d in devs:
        for k, v in op_seconds(tr, d, lo, hi).items():
            ops[k] += v / len(devs)
    idle: Dict[str, float] = defaultdict(float)
    index = _HostIndex(tr)
    for s, e in gaps(tr, devs[0], lo, hi):
        # gaps between back-to-back operations are launch overhead, not
        # host activity; they are grouped rather than labelled one by one
        label = host_label(tr, (s + e) / 2, prefer, index) \
            if e - s >= MIN_LABELLED_GAP_NS else "between_ops"
        idle[label] += (e - s) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "busy_s_per_device": dict(zip(devs, busy)),
            "device_ops": [[k, v] for k, v in rank(ops)],
            "idle_gaps": [[k, v] for k, v in rank(idle)]}
