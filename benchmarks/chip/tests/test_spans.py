"""The readers of the program's step spans, on a made-up trace whose
idle time is known to the millisecond."""
import pytest

from benchmarks.chip import harness, spans
from benchmarks.chip import trace as T

MS = 1e6                                       # ns
NEW = ("idle_share.sample", "idle_share.logits_fetch",
       "idle_share.untraced", "sample_ms_p50")


def _trace(host):
    # chip 0 works in [0, 20], [60, 70] and [95, 100] ms of a 100 ms
    # window: idle [20, 60] and [70, 95], 65 ms in all
    ops = {0: [(0, 20 * MS, "%fusion.1 = f32[8]"),
               (60 * MS, 70 * MS, "%fusion.2 = f32[8]"),
               (95 * MS, 100 * MS, "%fusion.3 = f32[8]")]}
    host = [(0, 100 * MS, T.WINDOW_SPAN)] + [
        (s * MS, e * MS, n) for s, e, n in host]
    return T.Trace(ops=ops, modules={}, host=sorted(host))


STEP = [
    (2, 92, "serve.step"),
    (2, 4, "admit"),
    (4, 88, "decode"),
    (4, 6, "decode.dispatch"),
    (6, 20, "device_wait"),                 # the chip works meanwhile
    (20, 30, "logits_fetch"),               # idle 10
    (21, 29, "np.asarray(jax.Array)"),      # JAX's: takes none of it
    (30, 50, "sample"),                     # idle 20
    (50, 55, "accept"),                     # idle 5; decode alone 55-58
    (58, 60, "decode.dispatch"),            # idle 2
    (60, 70, "device_wait"),
    (70, 75, "logits_fetch"),               # idle 5
    (75, 85, "sample"),                     # idle 10; decode alone 85-88
    (92, 94, "gen.submit"),                 # idle 2; 94-95 under nothing
]                                           # serve.step alone 88-92


def _read(name, tr):
    reader = harness.load_file(harness.CHIP / "metrics" / f"{name}.py",
                               f"test_metric_{name}")
    return reader.read({"trace": tr, "trace_devices": [0]})


def test_idle_by_span_accounts_for_every_idle_ms():
    idle = spans.idle_by_span(_trace(STEP), 0)
    want = {"logits_fetch": 15, "sample": 30, "accept": 5, "decode": 6,
            "decode.dispatch": 2, spans.UNTRACED: 4, "gen.submit": 2}
    assert idle.keys() == want.keys()
    for k, v in want.items():
        assert idle[k] == pytest.approx(v / 1e3), k
    # with the millisecond under no span, the whole idle time
    assert sum(idle.values()) + 1e-3 == pytest.approx(
        T.idle_percent(_trace(STEP), [0]) / 100 * 0.1)


def test_readers_exact_values():
    tr = _trace(STEP)
    assert _read("idle_share.sample", tr) == pytest.approx(30.0)
    assert _read("idle_share.logits_fetch", tr) == pytest.approx(15.0)
    assert _read("idle_share.untraced", tr) == pytest.approx(4.0)
    assert _read("sample_ms_p50", tr) == pytest.approx(15.0)


def test_spans_outside_the_window_are_not_read():
    late = STEP + [(99, 140, "sample")]     # opens in, runs past the end
    tr = _trace(late)
    assert _read("sample_ms_p50", tr) == pytest.approx(20.0)
    # 99-100 is busy: the share is unchanged
    assert _read("idle_share.sample", tr) == pytest.approx(30.0)


def test_a_child_outlasting_its_parent_is_cut_at_its_end():
    tr = _trace([(2, 92, "serve.step"), (10, 40, "decode"),
                 (30, 45, "sample")])
    idle = spans.idle_by_span(tr, 0)
    assert idle["sample"] == pytest.approx(0.010)        # 30-40
    assert idle["decode"] == pytest.approx(0.010)        # 20-30
    assert idle[spans.UNTRACED] == pytest.approx(0.042)  # 40-60, 70-92


def test_nothing_to_read_without_program_spans_or_the_chip():
    # a program from before the spans: the harness's own alone
    parent = _trace([(2, 92, "serve.step"),
                     (21, 29, "np.asarray(jax.Array)")])
    assert all(_read(n, parent) is None for n in NEW)
    # no plane of the cell's chip (a trace taken on the CPU)
    cpu = T.Trace(ops={}, modules={}, host=_trace(STEP).host)
    assert all(_read(n, cpu) is None for n in NEW)
    # spans, but none named: the idle share of a missing span is absent
    no_sample = _trace([iv for iv in STEP if iv[2] != "sample"])
    assert _read("idle_share.sample", no_sample) is None
    assert _read("sample_ms_p50", no_sample) is None
    assert _read("idle_share.untraced", no_sample) == pytest.approx(4.0)
