"""Serving telemetry: request tracing, Prometheus export, profiler hooks.

This module is the observability substrate for the serving stack:

* :class:`Tracer` — a bounded ring buffer of Chrome-trace events.  The
  scheduler emits one span chain per request (``enqueue`` → ``queued``
  → ``prefill`` chunk(s) → ``first_token`` → ``finish``/``shed``/
  ``cancel``) plus per-step phase spans; :func:`write_trace` or the
  gateway's ``GET /debug/trace`` export the buffer as Chrome-trace /
  Perfetto JSON so one file explains any slow request.
* :class:`ServeTelemetry` — the per-scheduler façade: owns the tracer,
  opens the step thread's spans (:meth:`ServeTelemetry.span`: each in
  the profiler trace and the ring, its wall time accumulated per phase
  for ``admit`` / ``prefill`` / ``decode`` / ``draft`` / ``verify``),
  and arms :func:`jax.profiler.start_trace` around a step window
  (``POST /debug/profile`` / ``--profile-steps``).
* :func:`prometheus_text` / :func:`scheduler_prometheus` — Prometheus
  text-format (0.0.4) exposition of every ``[serve]`` counter, the
  bounded latency histograms, per-``data``-shard page-pool occupancy,
  and per-rank series aggregated from mesh followers.
* :func:`stats_snapshot` — the compact JSON stats delta followers ship
  to host 0 each step over the plan channel's ``gather``.
* :func:`enable_json_logs` / :func:`log_event` — one-line structured
  JSON log records (``--log-json``) for report lines and
  hot-swap/shed events.

The tracing/JSON-log primitives live in :mod:`repro.telemetry` (shared
with the training stack so both emit one dialect) and are re-exported
here for backward compatibility.  Everything here is stdlib + jax;
nothing imports the scheduler, so the scheduler (and metrics) can
import this module freely.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from repro.telemetry import (  # noqa: F401  (re-exported surface)
    SCHED_TID,
    Span,
    Tracer,
    enable_json_logs,
    json_logs_enabled,
    log_event,
    prom_fmt as _fmt,
    write_trace,
)

__all__ = [
    "Tracer",
    "ServeTelemetry",
    "prometheus_text",
    "scheduler_prometheus",
    "stats_snapshot",
    "write_trace",
    "enable_json_logs",
    "json_logs_enabled",
    "log_event",
]

# the step phases whose wall time ``phase_seconds`` accumulates (and
# ``phase_seconds_total{phase=...}`` exports); every other span is a
# part of one of them
PHASES = frozenset({"admit", "prefill", "decode", "draft", "verify",
                    "surrogate_collect"})


class ServeTelemetry:
    """Per-scheduler telemetry: tracer + step spans + profiler.

    ``enabled=False`` keeps spans out of the ring and drops the request
    events (the cheap counters in :class:`~repro.serve.metrics.ServeStats`
    and ``phase_seconds`` stay on); the profiler annotations and window
    work regardless, so ``--profile-steps`` composes with
    ``--no-telemetry``.
    """

    def __init__(self, enabled: bool = True, trace_capacity: int = 8192):
        self.enabled = bool(enabled)
        self.tracer = Tracer(trace_capacity)
        # cumulative wall seconds per phase (PHASES), for /metrics
        self.phase_seconds: Dict[str, float] = {}
        self.phase_calls: Dict[str, int] = {}
        self._profile_req: Optional[tuple] = None  # (steps, outdir)
        self._profile_active = False
        self._profile_left = 0
        self._profile_dir: Optional[str] = None
        self.profiles_taken = 0
        self.profile_error: Optional[str] = None

    # ---- request lifecycle ------------------------------------------------

    def req_instant(self, rid: Any, name: str, t: Optional[float] = None,
                    **args: Any) -> None:
        """Emit an instant event on the request's trace row (if enabled)."""
        if self.enabled:
            self.tracer.req_instant(name, rid, t, **args)

    def req_span(self, rid: Any, name: str, t0: Optional[float], t1: float,
                 **args: Any) -> None:
        """Emit a complete span on the request's trace row (if enabled)."""
        if self.enabled and t0 is not None:
            self.tracer.req_span(name, rid, t0, t1, **args)

    def terminal(self, rid: Any, kind: str, t: Optional[float] = None,
                 **args: Any) -> None:
        """Emit the request's terminal instant: finish / shed / cancel."""
        if self.enabled:
            self.tracer.req_instant(kind, rid, t, terminal=True, **args)

    def event(self, name: str, **args: Any) -> None:
        """Emit a scheduler-level instant event (hot swap, drain, …)."""
        if self.enabled:
            self.tracer.instant(name, SCHED_TID, **args)

    # ---- step spans --------------------------------------------------------

    def span(self, name: str, **args: Any) -> Span:
        """A span on the scheduler row: in the profiler trace while it is
        open, in the ring when it closes (if enabled and ``emit`` is
        left true), and added to ``phase_seconds`` when ``name`` is one
        of :data:`PHASES`."""
        done = self._add_phase if name in PHASES else None
        if self.enabled:
            return self.tracer.span(name, SCHED_TID, done, **args)
        return Span(name, done=done, **args)

    def _add_phase(self, span: Span) -> None:
        name = span.name
        self.phase_seconds[name] = self.phase_seconds.get(name, 0.0) \
            + span.seconds
        self.phase_calls[name] = self.phase_calls.get(name, 0) + 1

    # ---- jax profiler window ----------------------------------------------

    def arm_profile(self, steps: int, outdir: str) -> None:
        """Arm ``jax.profiler`` around the next ``steps`` scheduler steps."""
        self._profile_req = (max(1, int(steps)), str(outdir))

    def profile_armed(self) -> bool:
        """Whether a profile window is pending or currently recording."""
        return self._profile_req is not None or self._profile_active

    def step_begin(self, step: int) -> None:
        """Scheduler-step hook: start the profiler if a window is armed."""
        if self._profile_req is None or self._profile_active:
            return
        steps, outdir = self._profile_req
        self._profile_req = None
        try:
            import jax

            jax.profiler.start_trace(outdir)
        except Exception as e:  # pragma: no cover - backend-dependent
            self.profile_error = f"{type(e).__name__}: {e}"
            log_event("profile_error", error=self.profile_error)
            return
        self._profile_active = True
        self._profile_left = steps
        self._profile_dir = outdir
        log_event("profile_start", steps=steps, dir=outdir, step=step)

    def step_end(self) -> None:
        """Scheduler-step hook: stop the profiler when the window closes."""
        if not self._profile_active:
            return
        self._profile_left -= 1
        if self._profile_left > 0:
            return
        self._profile_active = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend-dependent
            self.profile_error = f"{type(e).__name__}: {e}"
            log_event("profile_error", error=self.profile_error)
            return
        self.profiles_taken += 1
        log_event("profile_done", dir=self._profile_dir)


# ---- mesh stats snapshot --------------------------------------------------

# every [serve] counter a follower ships to host 0 (and prometheus
# exports per rank); gauges (queue/slots/pool) ride alongside
_SNAPSHOT_COUNTERS = (
    "submitted",
    "completed",
    "rejected",
    "shed_overload",
    "shed_deadline",
    "cancelled",
    "ttft_deadline_misses",
    "tpot_deadline_misses",
    "prefills",
    "prefill_chunks",
    "prefill_tokens",
    "padded_prefill_tokens",
    "decode_steps",
    "decode_tokens",
    "decode_slot_steps",
    "sample_fetch_bytes",
    "moe_dropped_tokens",
    "ragged_splits",
    "spec_rounds",
    "spec_draft_steps",
    "spec_draft_proposed",
    "spec_draft_accepted",
    "spec_replays",
    "steps",
    "hot_swaps",
    "fault_injected",
    "swap_rejected_corrupt",
    "plan_retries",
    "journal_replayed",
    "arena_matches",
    "arena_promotions",
)


def _pool_shards(sched: Any) -> List[dict]:
    """Per-``data``-shard block-manager dicts for a scheduler's pool."""
    pool = getattr(sched, "pool", None)
    if pool is None:
        return []
    shards = getattr(pool, "shards", None)
    if shards:
        return [sh.blocks.as_dict() for sh in shards]
    blocks = getattr(pool, "blocks", None)
    return [blocks.as_dict()] if blocks is not None else []


def stats_snapshot(sched: Any, rank: int = 0) -> dict:
    """Compact per-process stats delta for mesh-wide aggregation.

    Followers JSON-encode this and ship it to host 0 on the plan
    channel's ``gather`` path each step; host 0 keeps the latest
    snapshot per rank in ``sched.remote_stats`` and the Prometheus
    export emits it as per-rank series.
    """
    s = sched.stats
    snap: Dict[str, Any] = {"rank": int(rank)}
    for k in _SNAPSHOT_COUNTERS:
        snap[k] = int(getattr(s, k, 0))
    snap["queue_depth"] = len(getattr(sched, "queue", ()))
    snap["slots_busy"] = len(getattr(sched, "active", ())) + len(
        getattr(sched, "prefilling", ())
    )
    snap["shards"] = _pool_shards(sched)
    arena = getattr(sched, "arena", None)
    if arena is not None:
        snap["arena"] = arena.counters()
    return snap


# ---- prometheus exposition ------------------------------------------------

_PREFIX = "repro_serve_"

_COUNTER_HELP = {
    "submitted": "requests submitted",
    "completed": "requests completed",
    "rejected": "requests rejected at submit (queue full)",
    "shed_overload": "requests shed for overload",
    "shed_deadline": "queued requests shed on expired TTFT deadline",
    "cancelled": "requests cancelled",
    "ttft_deadline_misses": "completions whose first token was late",
    "tpot_deadline_misses": "completions whose mean TPOT was over budget",
    "prefills": "prefill dispatches",
    "prefill_chunks": "chunked-prefill slices",
    "prefill_tokens": "prompt tokens prefilled",
    "padded_prefill_tokens": "prompt tokens incl. bucket padding",
    "decode_steps": "batched decode steps",
    "decode_tokens": "tokens decoded",
    "decode_slot_steps": "per-slot decode steps",
    "sample_fetch_bytes": "bytes copied to the host for sampling "
                          "(the drawn token ids)",
    "moe_dropped_tokens": "routed expert choices dropped for capacity "
                          "(as last read from the device)",
    "ragged_splits": "ragged gather-width split dispatches",
    "spec_rounds": "speculative verify rounds",
    "spec_draft_steps": "drafter decode dispatches",
    "spec_draft_proposed": "draft tokens proposed",
    "spec_draft_accepted": "draft tokens accepted",
    "spec_replays": "speculative rollback replay steps",
    "steps": "scheduler steps",
    "hot_swaps": "weight hot swaps applied",
    "fault_injected": "harness faults fired (--fault-spec)",
    "swap_rejected_corrupt":
        "hot swaps rejected on a corrupt/torn winner checkpoint",
    "plan_retries": "mesh plan-channel fetch retries before success",
    "journal_replayed": "requests requeued from the request journal",
    "arena_matches": "online-LTFB arena match evaluations",
    "arena_promotions": "online-LTFB arena champion promotions",
}

_SHARD_GAUGES = {
    "used_blocks": "KV pages currently allocated",
    "committed_blocks": "KV pages reserved by admitted requests",
    "pinned_blocks": "KV pages pinned by the prefix pin tier",
    "high_water_blocks": "peak KV pages allocated",
    "num_blocks": "KV page capacity",
}


def _hist_lines(out: List[str], name: str, help_: str, series: Any) -> None:
    """Append one histogram family from a BoundedSeries to ``out``."""
    out.append(f"# HELP {name} {help_}")
    out.append(f"# TYPE {name} histogram")
    cum = 0
    for le, n in series.hist.bucket_counts():
        cum += n
        out.append(f'{name}_bucket{{le="{_fmt(le)}"}} {cum}')
    out.append(f'{name}_bucket{{le="+Inf"}} {series.hist.total}')
    out.append(f"{name}_sum {_fmt(series.hist.sum)}")
    out.append(f"{name}_count {series.hist.total}")


def _arena_lines(out: List[str], arena: dict) -> None:
    """Append the online-LTFB arena families (per-member accept-rate /
    served-token gauges + the promotion counter) from an
    ``Arena.counters()`` dict."""
    members = arena.get("members", {})
    fams = (
        ("accept_rate", "gauge",
         "per-member sliding-window spec accept rate",
         lambda m: m.get("accept_rate", 0.0)),
        ("served_tokens", "gauge",
         "tokens served while the member was champion",
         lambda m: int(m.get("served_tokens", 0))),
    )
    for suffix, typ, help_, get in fams:
        name = f"{_PREFIX}arena_{suffix}"
        out.append(f"# HELP {name} {help_}")
        out.append(f"# TYPE {name} {typ}")
        for member in sorted(members):
            out.append(f'{name}{{member="{member}"}} '
                       f"{_fmt(get(members[member]))}")
    name = f"{_PREFIX}arena_promotions_total"
    out.append(f"# HELP {name} arena champion promotions")
    out.append(f"# TYPE {name} counter")
    out.append(f"{name} {int(arena.get('promotions', 0))}")


def _mesh_arena_lines(out: List[str], ranked: List[tuple]) -> None:
    """Per-rank arena member series (``{rank=,member=}``) — one
    HELP/TYPE header per family, samples for every rank under it."""
    fams = (
        ("accept_rate", "gauge",
         "per-rank per-member spec accept rate",
         lambda m: m.get("accept_rate", 0.0)),
        ("served_tokens", "gauge",
         "per-rank tokens served while the member was champion",
         lambda m: int(m.get("served_tokens", 0))),
    )
    for suffix, typ, help_, get in fams:
        name = f"{_PREFIX}mesh_arena_{suffix}"
        out.append(f"# HELP {name} {help_}")
        out.append(f"# TYPE {name} {typ}")
        for rank, arena in ranked:
            for member in sorted(arena.get("members", {})):
                out.append(
                    f'{name}{{rank="{rank}",member="{member}"}} '
                    f"{_fmt(get(arena['members'][member]))}")
    name = f"{_PREFIX}mesh_arena_promotions_total"
    out.append(f"# HELP {name} per-rank arena champion promotions")
    out.append(f"# TYPE {name} counter")
    for rank, arena in ranked:
        out.append(f'{name}{{rank="{rank}"}} '
                   f"{int(arena.get('promotions', 0))}")


def prometheus_text(
    stats: Any,
    pool_shards: Optional[List[dict]] = None,
    phase_seconds: Optional[Dict[str, float]] = None,
    remote_stats: Optional[Dict[int, dict]] = None,
    queue_depth: Optional[int] = None,
    slots_busy: Optional[int] = None,
    arena: Optional[dict] = None,
) -> str:
    """Render a ServeStats (+ pool/phase/mesh context) as Prometheus text.

    Exposition format 0.0.4: ``# HELP`` / ``# TYPE`` per family,
    counters suffixed ``_total``, latency histograms with cumulative
    ``_bucket{le=...}`` + ``_sum`` + ``_count``, per-shard pool gauges
    labelled ``{shard=...}``, per-rank mesh series labelled
    ``{rank=...}`` from the follower snapshots, and — when an
    online-LTFB arena is live (``arena`` is :meth:`Arena.counters`
    output) — per-member ``{member=...}`` accept-rate / served-token
    series plus the promotion counter.
    """
    out: List[str] = []
    for k, help_ in _COUNTER_HELP.items():
        name = f"{_PREFIX}{k}_total"
        out.append(f"# HELP {name} {help_}")
        out.append(f"# TYPE {name} counter")
        out.append(f"{name} {int(getattr(stats, k, 0))}")

    wall = stats.wall
    gauges = [
        ("wall_seconds", "serving wall-clock seconds", wall),
        ("slots", "decode slot capacity", getattr(stats, "slots", 0)),
    ]
    if queue_depth is not None:
        gauges.append(("queue_depth", "requests waiting for admission",
                       queue_depth))
    if slots_busy is not None:
        gauges.append(("slots_busy", "slots prefilling or decoding",
                       slots_busy))
    if getattr(stats, "latent_cache_bytes_per_token", 0):
        gauges.append(("latent_cache_bytes_per_token",
                       "latent pool bytes held per token over every layer",
                       stats.latent_cache_bytes_per_token))
    d = stats.as_dict()
    for k in ("tokens_per_s", "requests_per_s", "spec_accept_rate",
              "spec_k_mean", "queue_depth_mean", "slot_occupancy"):
        v = d.get(k)
        if v is not None:
            gauges.append((k, k.replace("_", " "), v))
    for k, help_, v in gauges:
        name = f"{_PREFIX}{k}"
        out.append(f"# HELP {name} {help_}")
        out.append(f"# TYPE {name} gauge")
        out.append(f"{name} {_fmt(v)}")

    routed = getattr(stats, "moe_routed_tokens", None)
    if routed:
        name = f"{_PREFIX}moe_routed_tokens_total"
        out.append(f"# HELP {name} real tokens routed to each expert, "
                   "summed over layers (as last read from the device)")
        out.append(f"# TYPE {name} counter")
        for e, n in enumerate(routed):
            out.append(f'{name}{{expert="{e}"}} {int(n)}')

    _hist_lines(out, f"{_PREFIX}ttft_seconds", "time to first token",
                stats.ttft)
    _hist_lines(out, f"{_PREFIX}tpot_seconds", "time per output token",
                stats.tpot)
    _hist_lines(out, f"{_PREFIX}latency_seconds", "request latency",
                stats.latency)

    if phase_seconds:
        name = f"{_PREFIX}phase_seconds_total"
        out.append(f"# HELP {name} cumulative wall seconds per step phase")
        out.append(f"# TYPE {name} counter")
        for ph in sorted(phase_seconds):
            out.append(f'{name}{{phase="{ph}"}} {_fmt(phase_seconds[ph])}')

    if pool_shards:
        for k, help_ in _SHARD_GAUGES.items():
            name = f"{_PREFIX}pool_{k}"
            out.append(f"# HELP {name} {help_} (per data shard)")
            out.append(f"# TYPE {name} gauge")
            for i, sh in enumerate(pool_shards):
                out.append(f'{name}{{shard="{i}"}} {int(sh.get(k, 0))}')

    if arena:
        _arena_lines(out, arena)

    if remote_stats:
        name = f"{_PREFIX}mesh"
        out.append(f"# HELP {name}_counters per-rank mesh counters")
        for k in _SNAPSHOT_COUNTERS:
            fam = f"{name}_{k}_total"
            out.append(f"# TYPE {fam} counter")
            for rank in sorted(remote_stats):
                snap = remote_stats[rank]
                out.append(f'{fam}{{rank="{rank}"}} {int(snap.get(k, 0))}')
        fam = f"{name}_pool_high_water_blocks"
        out.append(f"# HELP {fam} peak KV pages per rank and data shard")
        out.append(f"# TYPE {fam} gauge")
        for rank in sorted(remote_stats):
            for i, sh in enumerate(remote_stats[rank].get("shards", [])):
                out.append(
                    f'{fam}{{rank="{rank}",shard="{i}"}} '
                    f"{int(sh.get('high_water_blocks', 0))}"
                )
        ranked = [(r, remote_stats[r]["arena"])
                  for r in sorted(remote_stats)
                  if remote_stats[r].get("arena")]
        if ranked:
            _mesh_arena_lines(out, ranked)
    return "\n".join(out) + "\n"


def scheduler_prometheus(sched: Any) -> str:
    """Prometheus text for a live scheduler (stats + pool + mesh +
    phases + online-LTFB arena when one is attached)."""
    tel = getattr(sched, "telemetry", None)
    arena = getattr(sched, "arena", None)
    return prometheus_text(
        sched.stats,
        pool_shards=_pool_shards(sched),
        phase_seconds=tel.phase_seconds if tel is not None else None,
        remote_stats=getattr(sched, "remote_stats", None),
        queue_depth=len(getattr(sched, "queue", ())),
        slots_busy=len(getattr(sched, "active", ()))
        + len(getattr(sched, "prefilling", ())),
        arena=arena.counters() if arena is not None else None,
    )
