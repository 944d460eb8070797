"""The open-loop session generator and the JAG sample generator."""
import json

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.traffic import jag, sessions

P = json.loads((harness.CHIP / "traffic" / "chat-shared.json").read_text())
V = 151936


@pytest.fixture(scope="module")
def mixes():
    return {s: sessions.generate(P, s, 30, V)
            for s in (1, 2, 2 ** 31 + 12345)}


def test_same_seed_same_requests(mixes):
    again = sessions.generate(P, 1, 30, V)
    for a, b in zip(mixes[1], again):
        assert a.due == b.due and a.max_new == b.max_new
        assert np.array_equal(a.prompt, b.prompt)
        assert (a.temperature, a.sample_seed) == (b.temperature,
                                                  b.sample_seed)


def test_every_seed_offers_the_same_work(mixes):
    """Every seed offers the same requests (sizes, sessions, greedy or
    not) at the same times; only the tokens and sampling seeds differ."""
    def shape(reqs):
        return [(r.due, len(r.prompt), r.max_new, r.greedy, r.session,
                 r.turn, r.system) for r in reqs]

    views = list(mixes.values())
    assert all(shape(m) == shape(views[0]) for m in views[1:])
    window = [r for r in views[0] if r.due >= 0]
    assert len(window) == round(P["rate_per_s"] * 30)
    assert not np.array_equal(mixes[1][0].prompt, mixes[2][0].prompt)
    assert mixes[1][0].sample_seed != mixes[2][0].sample_seed


def test_sizes_and_sessions(mixes):
    shortest, longest, total = sessions.bounds(P)
    for reqs in mixes.values():
        assert all(shortest <= len(r.prompt) <= longest
                   and len(r.prompt) + r.max_new <= total for r in reqs)
        dues = [r.due for r in reqs]
        assert dues == sorted(dues) and dues[0] == -P["preroll_s"]
        for r in reqs:
            assert 0 < len(r.prompt) <= P["max_prompt_tokens"]
            assert P["output_tokens"]["lo"] <= r.max_new \
                <= P["output_tokens"]["hi"]
            assert r.prompt.dtype == np.int32
            assert 0 <= r.prompt.min() and r.prompt.max() < V
            assert r.greedy == (r.temperature == 0.0)
        by_session = {}
        for r in reqs:
            by_session.setdefault(r.session, []).append(r)
        for turns in by_session.values():
            sys_len = P["system_prompt_tokens"]
            for a, b in zip(turns, turns[1:]):
                # a later turn carries the whole earlier prompt, an
                # answer of the earlier turn's length and a new message
                assert b.turn == a.turn + 1
                assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
                assert len(b.prompt) > len(a.prompt) + a.max_new
            assert len({t.system for t in turns}) == 1
            first = turns[0].prompt[:sys_len]
            assert all(np.array_equal(t.prompt[:sys_len], first)
                       for t in turns)


def test_system_prompts_are_shared(mixes):
    reqs = mixes[1]
    heads = {r.prompt[:P["system_prompt_tokens"]].tobytes() for r in reqs}
    assert len(heads) <= P["system_prompts"]


def test_jag_matches_the_simulator_it_copies():
    """The device generator gives the program's synthetic JAG samples for
    the same inputs (the benchmark keeps its own copy of the formulas)."""
    from repro.data import jag as prog

    got = jag.generate(16, 8, seed=5)
    want = prog.jag_simulate(got["x"], image_size=8)
    for k in ("scalars", "images"):
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=2e-6)
    assert got["x"].shape == (16, 5)
    assert (0 <= got["x"]).all() and (got["x"] < 1).all()
    again = jag.generate(16, 8, seed=5)
    assert np.array_equal(again["images"], got["images"])
    other = jag.generate(16, 8, seed=6)
    assert not np.array_equal(other["x"], got["x"])
