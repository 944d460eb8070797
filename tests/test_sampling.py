"""The device sampler (``repro.serve.sampling``): greedy rows are the
exact argmax, a draw depends only on its row's logits, temperature, seed
and stream position, and sampled tokens follow ``softmax(logits / T)``."""
import dataclasses

import jax
import numpy as np
import pytest
from scipy import stats

from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve.sampling import sample_rows, seed_words
from repro.serve.scheduler import Request, Scheduler


def _draw(logits, temperature, seed, position):
    return np.asarray(sample_rows(jax.numpy.asarray(logits), temperature,
                                  seed, position))


def test_greedy_rows_are_the_exact_argmax():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((6, 3, 1000)).astype(np.float32)
    logits[2, 1, 17] = logits[2, 1, 900] = 50.0     # a tie: first index
    want = np.argmax(logits, axis=-1)
    got = _draw(logits, [0.0] * 6, [7] * 6, np.arange(18).reshape(6, 3))
    assert got.dtype == np.int32 and np.array_equal(got, want)
    flat = _draw(logits[:, 0], [-1.0] * 6, [0] * 6, np.zeros(6))
    assert np.array_equal(flat, want[:, 0])


def test_a_draw_ignores_batch_slot_group_and_depth():
    """One row at (seed, position) draws the same token alone, as one
    slot of a larger batch, in either group of a split batch, and as
    one token of a K-deep block."""
    rng = np.random.default_rng(1)
    V, B = 4096, 8
    rows = rng.standard_normal((B, V)).astype(np.float32) * 3
    temp = [0.7, 1.0, 0.0, 1.3, 0.7, 2.0, 0.5, 1.0]
    seed = [11, 2**40 + 3, 5, 0, 11, 9, 2**63, 4]
    pos = np.array([0, 3, 8, 1, 2, 40, 7, 5])
    batch = _draw(rows, temp, seed, pos)
    alone = [_draw(rows[b], [temp[b]], [seed[b]], [pos[b]])[0]
             for b in range(B)]
    assert np.array_equal(batch, alone)
    perm = rng.permutation(B)
    moved = _draw(rows[perm], [temp[b] for b in perm],
                  [seed[b] for b in perm], pos[perm])
    assert np.array_equal(moved, batch[perm])
    for group in (perm[:3], perm[3:]):
        got = _draw(rows[group], [temp[b] for b in group],
                    [seed[b] for b in group], pos[group])
        assert np.array_equal(got, batch[group])
    # a K = 3 block: row b's column k holds the logits at position
    # pos[b] + k (the row's own logits at k = 1)
    K = 3
    block = rng.standard_normal((B, K, V)).astype(np.float32)
    block[:, 1] = rows
    deep = _draw(block, temp, seed, (pos - 1)[:, None] + np.arange(K))
    assert np.array_equal(deep[:, 1], batch)


def test_positions_and_seeds_draw_independently():
    """Over a flat distribution, the tokens along positions spread over
    the vocabulary, and two seeds (or two position ranges) agree no more
    often than chance."""
    V, n = 64, 4096
    flat = np.zeros((n, V), np.float32)
    pos = np.arange(n)

    def stream(seed, start=0):
        return _draw(flat, [1.0] * n, [seed] * n, pos + start)

    a, b, shifted = stream(3), stream(4), stream(3, start=n)
    assert np.array_equal(a, stream(3))
    for other in (b, shifted):
        agree = float(np.mean(a == other))
        assert agree < 3.0 / V, agree
    assert len(np.unique(a)) == V


@pytest.mark.parametrize("temperature", [0.7, 1.5])
def test_frequencies_follow_the_tempered_softmax(temperature):
    """~20k draws over 8 logits match ``softmax(logits / T)`` by a
    chi-square test, and reject the distribution of the other
    temperature."""
    logits = np.array([2.0, 1.0, 0.5, 0.0, -0.5, -1.0, 1.5, -2.0],
                      np.float32)
    n = 20_000
    got = _draw(np.broadcast_to(logits, (n, 8)), [temperature] * n,
                [2024] * n, np.arange(n))
    counts = np.bincount(got, minlength=8)

    def expected(t):
        z = logits.astype(np.float64) / t
        p = np.exp(z - z.max())
        return n * p / p.sum()

    assert stats.chisquare(counts, expected(temperature)).pvalue > 1e-3
    other = 1.5 if temperature == 0.7 else 0.7
    assert stats.chisquare(counts, expected(other)).pvalue < 1e-6


def test_wide_seeds_keep_their_high_bits():
    V, n = 1000, 256
    flat = np.zeros((n, V), np.float32)
    low = 0x1234_5678
    wide = (5 << 32) | low

    def stream(seed):
        return _draw(flat, [1.0] * n, [seed] * n, np.arange(n))

    assert seed_words(wide) == (low, 5)
    assert float(np.mean(stream(wide) == stream(low))) < 0.05
    for bad in (-1, 2**64):
        with pytest.raises(ValueError):
            seed_words(bad)


def test_scheduler_rejects_a_seed_the_sampler_cannot_key():
    cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                              dtype="float32")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    sched = Scheduler(cfg, params, num_slots=2, max_len=32, block_size=4)
    prompt = np.arange(1, 5, dtype=np.int32)
    for i, seed in enumerate((-3, 2**64, "7")):
        with pytest.raises(ValueError):
            sched.submit(Request(rid=i, prompt=prompt, max_new=2,
                                 temperature=0.7, seed=seed))
    assert sched.stats.rejected == 3 and not sched.queue
    sched.submit(Request(rid="ok", prompt=prompt, max_new=2,
                         temperature=0.7, seed=2**64 - 1))
    assert len(sched.run()["ok"]) == 2
