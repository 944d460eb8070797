"""The device's idle time put down to the program's own host spans.

The serving scheduler opens a profiler annotation at each boundary of
its step (``ServeTelemetry.span`` in ``repro.serve.telemetry``): the
phases ``admit``, ``prefill``, ``decode``, ``draft`` and ``verify``;
inside them ``kv.ensure``, ``prefill.dispatch``, ``decode.dispatch``,
``device_wait``, ``logits_fetch``, ``sample``, ``accept`` and
``kv.register_prefix``; and ``journal`` at step level.  They sit on the
harness thread's line, inside its ``serve.step`` spans.

Each instant of the traced window belongs to the innermost span that
covers it among these and the harness's own (``harness.SPANS``); JAX's
annotations nested inside (``np.asarray(jax.Array)`` within
``logits_fetch``) take nothing, so a span's share is its self time: its
interval less those of the program spans nested in it.  Time in
``serve.step`` that no program span covers is ``untraced``: what the
program's spans still miss.

A trace that holds no program span (a program from before they were
added), or no plane of the cell's chip (the CPU's), gives nothing.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import harness, trace

PROGRAM_SPANS = frozenset({
    "admit", "prefill", "decode", "draft", "verify", "journal",
    "kv.ensure", "kv.register_prefix", "prefill.dispatch",
    "decode.dispatch", "device_wait", "logits_fetch", "sample", "accept",
})
STEP_SPAN = "serve.step"
UNTRACED = "untraced"
_LABELS = PROGRAM_SPANS | set(harness.SPANS)


def segments(tr: trace.Trace, lo: float, hi: float
             ) -> List[Tuple[float, float, Optional[str]]]:
    """[lo, hi] cut wherever a program or harness span opens or closes:
    (start_ns, end_ns, name of the innermost such span or None), in
    order.  Spans of one thread nest; a child that outlasts its parent
    (by the clocks' rounding) is cut at the parent's end."""
    spans = sorted(((s, e, n) for s, e, n in tr.host
                    if n in _LABELS and e > lo and s < hi),
                   key=lambda iv: (iv[0], -iv[1]))
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Tuple[float, str]] = []          # (end, name), innermost last
    cur = lo

    def advance(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end
        if t > cur:
            out.append((cur, t, stack[-1][1] if stack else None))
            cur = t

    for s, e, name in spans:
        advance(max(s, lo))
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    advance(hi)
    return out


def idle_by_span(tr: trace.Trace, dev: int) -> Dict[str, float]:
    """Seconds of ``dev``'s idle time in the traced window by the
    innermost span covering them (``untraced`` for ``serve.step``
    alone); idle time outside every span is left out."""
    lo, hi = tr.window()
    segs = segments(tr, lo, hi)
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for gs, ge in trace.gaps(tr, dev, lo, hi):
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, name = segs[j]
            if name is not None:
                label = UNTRACED if name == STEP_SPAN else name
                out[label] += (min(e, ge) - max(s, gs)) / 1e9
            j += 1
    return dict(out)


def readable(obs) -> Optional[trace.Trace]:
    """The run's trace, when it holds the cell's first chip and some
    program span in the traced window; else None."""
    tr = obs.get("trace")
    if tr is None or obs["trace_devices"][0] not in tr.ops:
        return None
    lo, hi = tr.window()
    if not any(n in PROGRAM_SPANS and s < hi and e > lo
               for s, e, n in tr.host):
        return None
    return tr


def in_window(tr: trace.Trace, name: str) -> List[float]:
    """Durations in seconds of the spans named ``name`` that open in the
    traced window."""
    lo, hi = tr.window()
    return [(e - s) / 1e9 for s, e, n in tr.host
            if n == name and lo <= s < hi]


def idle_share(obs, name: str) -> Optional[float]:
    """Percent of the traced window in which chip 0 sat idle inside the
    self time of the spans named ``name``; None when the trace holds no
    such span."""
    tr = readable(obs)
    if tr is None or not in_window(tr, name):
        return None
    lo, hi = tr.window()
    idle = idle_by_span(tr, obs["trace_devices"][0])
    return 100.0 * idle.get(name, 0.0) / ((hi - lo) / 1e9)
