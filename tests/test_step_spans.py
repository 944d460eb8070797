"""The scheduler's step spans: each opened as a profiler annotation on
the thread that steps the scheduler (so a profile puts the device's idle
time down to them), nested inside its phase, recorded in the ring under
the same name, present in the profile with ``telemetry=False`` too, and
as many per step whatever the number of active rows.  The LTFB loop's
main-thread spans reach the profiler the same way."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.population import Population, TrainerFns
from repro.models.lm import init_lm
from repro.serve.journal import RequestJournal
from repro.serve.scheduler import Request, Scheduler
from repro.telemetry import SCHED_TID

MARK = "test.step"
PHASES = {"admit", "prefill", "decode", "journal"}
# span -> the phases it may sit in (directly: no other program span
# lies between them)
PARENTS = {
    "kv.ensure": {"prefill", "decode"},
    "prefill.dispatch": {"prefill"},
    "kv.register_prefix": {"prefill"},
    "device_wait": {"prefill", "decode", "draft", "verify"},
    "decode.dispatch": {"decode", "draft", "verify"},
    "sample": {"prefill", "decode", "draft", "verify"},
    "accept": {"decode"},
    "draft": {"decode"},
    "verify": {"decode"},
}
SPANS = PHASES | set(PARENTS)


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                              dtype="float32")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _scheduler(served, n_requests, max_new=6, **kw):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=4, max_len=48, block_size=4,
                      **kw)
    rng = np.random.default_rng(5)
    for i in range(n_requests):
        sched.submit(Request(rid=f"r{i}", max_new=max_new, temperature=0.7,
                             seed=i, prompt=rng.integers(
                                 0, cfg.vocab_size, 9).astype(np.int32)))
    return sched


def _profiled_steps(path, runs):
    """Call each ``(step, times)`` under the profiler, each call in a
    ``test.step`` annotation; returns the host events (start, end,
    name) of the line that holds the marks: the caller's thread."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        for step, times in runs:
            for _ in range(times):
                with jax.profiler.TraceAnnotation(MARK):
                    step()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (xplane,) = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(xplane)
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in line.events] for line in host.lines]
    (own,) = [evs for evs in lines if any(n == MARK for _, _, n in evs)]
    return sorted(own, key=lambda iv: (iv[0], -iv[1]))


def _parent(ev, events):
    """The innermost program span (or mark) that encloses ``ev``."""
    s, e, _ = ev
    outer = [iv for iv in events if iv is not ev and iv[0] <= s
             and e <= iv[1] and iv[2] in SPANS | {MARK}]
    return max(outer, key=lambda iv: (iv[0], -iv[1]))[2] if outer else None


def _ring_spans(sched):
    return [(ev["ts"], ev["ts"] + ev["dur"], ev["name"])
            for ev in sched.telemetry.tracer.events
            if ev["ph"] == "X" and ev["tid"] == SCHED_TID]


@pytest.fixture(scope="module")
def profiled(served, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    journal = RequestJournal(str(tmp / "journal.log"))
    on = _scheduler(served, 2, journal=journal)
    off = _scheduler(served, 2, telemetry=False)
    # enough steps to admit, prefill, decode and finish both requests
    events = _profiled_steps(tmp / "trace", [(on.step, 8), (off.step, 8)])
    journal.close()
    marks = [iv for iv in events if iv[2] == MARK]
    split = marks[8][0]
    return (on, [iv for iv in events if iv[0] < split],
            off, [iv for iv in events if iv[0] >= split])


def test_every_span_lands_in_its_phase_on_the_callers_line(profiled):
    _, events, _, _ = profiled
    names = {n for _, _, n in events}
    assert SPANS - {"draft", "verify"} <= names
    # tokens are drawn on the device: no logits cross to the host
    assert "logits_fetch" not in names
    for ev in events:
        name = ev[2]
        if name in PHASES:
            assert _parent(ev, events) == MARK, ev
        elif name in PARENTS:
            assert _parent(ev, events) in PARENTS[name], ev


def test_ring_and_profiler_name_the_same_spans(profiled):
    on, events, _, _ = profiled
    ring = {n for _, _, n in _ring_spans(on)}
    assert ring == {n for _, _, n in events if n in SPANS}
    # a phase with nothing to do is in the profile but not in the ring
    assert sum(n == "admit" for *_, n in _ring_spans(on)) \
        < sum(n == "admit" for *_, n in events)


def test_telemetry_off_keeps_the_profiler_spans(profiled):
    _, on_events, off, off_events = profiled
    assert len(off.telemetry.tracer.events) == 0
    assert off.telemetry.phase_seconds["decode"] > 0
    on_names = {n for _, _, n in on_events if n in SPANS} - {"journal"}
    assert {n for _, _, n in off_events if n in SPANS} == on_names


def test_spans_per_step_do_not_grow_with_rows(served, tmp_path):
    """A decode step with one active row and one with four open the
    same spans: one ``sample`` (the device sampler's dispatch) and no
    ``logits_fetch``."""
    one, four = (_scheduler(served, n, max_new=12) for n in (1, 4))
    for sched, n in ((one, 1), (four, 4)):
        while len(sched.active) < n:       # admit + prefill, unprofiled
            sched.step()
    events = _profiled_steps(tmp_path, [(one.step, 1), (four.step, 1)])
    marks = [iv for iv in events if iv[2] == MARK]
    counts = []
    for s, e, _ in marks:
        inside = sorted(n for a, b, n in events
                        if s <= a and b <= e and n in SPANS)
        assert inside.count("sample") == 1 and "accept" in inside
        assert not any(n == "logits_fetch" for a, b, n in events
                       if s <= a and b <= e)
        counts.append(inside)
    assert len(one.active) == 1 and len(four.active) == 4
    assert counts[0] == counts[1]


def test_speculative_round_spans(served):
    """A speculative round puts dispatch, sampling and the wait for the
    drawn ids inside ``draft`` and ``verify``, and acceptance directly
    in ``decode`` (read from the ring, which holds the same spans)."""
    cfg, params = served
    sched = _scheduler(served, 2, draft_params=params, spec_tokens=2)
    sched.run()
    spans = _ring_spans(sched)
    names = {n for _, _, n in spans}
    assert {"draft", "verify", "decode.dispatch", "device_wait",
            "sample", "accept"} <= names
    assert "logits_fetch" not in names
    for ev in spans:
        if ev[2] in PARENTS:
            assert _parent(ev, spans) in PARENTS[ev[2]], ev
    in_draft = {n for n in names if any(
        _parent(ev, spans) == "draft" for ev in spans if ev[2] == n)}
    in_verify = {n for n in names if any(
        _parent(ev, spans) == "verify" for ev in spans if ev[2] == n)}
    assert {"decode.dispatch", "device_wait", "sample"} <= in_draft
    assert {"decode.dispatch", "device_wait", "sample"} <= in_verify


def test_only_token_ids_are_fetched_for_sampling(served):
    """Each full-batch decode step copies ``num_slots`` int32 ids to the
    host for sampling, and each first token one more: no logits."""
    sched = _scheduler(served, 3)
    sched.run()
    st = sched.stats
    assert st.completed == 3 and st.decode_steps > 0
    assert st.ragged_splits == 0
    assert st.sample_fetch_bytes == 4 * (sched.pool.num_slots
                                         * st.decode_steps + st.completed)
    assert st.as_dict()["sample_fetch_bytes"] == st.sample_fetch_bytes


def test_training_spans_reach_the_profiler_without_telemetry(tmp_path):
    """``train_round`` > ``data_wait`` / ``step`` and ``tournament`` are
    profiler annotations on the calling thread even with no
    ``TrainTelemetry`` attached."""
    def init(seed):
        return {"w": jnp.full((1,), float(seed))}, {}, {"lr": 0.1}

    @jax.jit
    def train_step(params, opt_state, batch, hparams):
        return {"w": params["w"] - hparams["lr"] * (params["w"] - batch)}, \
            opt_state, {"loss": jnp.sum(params["w"])}

    batch = jnp.ones((1,))
    pop = Population(TrainerFns(init, train_step,
                                jax.jit(lambda p, b: jnp.sum(p["w"] - b))),
                     [lambda: batch] * 2, [[batch]] * 2)
    assert pop.telemetry is None

    def round_and_tournament():
        pop.train_round(2)
        pop.tournament()

    events = _profiled_steps(tmp_path, [(round_and_tournament, 1)])
    names = [n for _, _, n in events]
    assert names.count("train_round") == 2 and names.count("step") == 4
    assert names.count("data_wait") == 4 and names.count("tournament") == 1
    for ev in events:
        if ev[2] in ("data_wait", "step"):
            outer = [iv for iv in events if iv[0] <= ev[0]
                     and ev[1] <= iv[1] and iv[2] == "train_round"]
            assert len(outer) == 1, ev
