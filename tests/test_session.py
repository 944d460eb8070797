"""Decode-API tests: the family-agnostic DecodeSession over the paged
layout, K-token write/verify parity, recurrent snapshot/restore
round-trips, population speculative decoding (token-identity vs
target-only decode), prefix pinning, and the ragged gather-width
split."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import replace
from repro.configs.registry import get_config
from repro.models.lm import init_cache, init_lm, lm_decode, lm_prefill
from repro.serve import session as session_mod
from repro.serve.kv_cache import PagedLayout
from repro.serve.scheduler import Request, Scheduler
from repro.serve.session import DecodeSession

KEY = jax.random.PRNGKey(0)


def _f32_cfg(arch: str):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    if cfg.moe is not None:   # dropless so train-mode forward matches
        cfg = replace(cfg, **{
            "moe.capacity_factor": float(cfg.moe.num_experts)})
    return cfg


def _prompts(cfg, n, max_len, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n, max_len), 0, cfg.vocab_size), np.int32)


def _prefilled(cfg, params, toks, total, block_size=4):
    """A session over a fresh paged layout with row b of ``toks``
    prefilled as request b in slot b, pages ensured for ``total``
    tokens."""
    B = toks.shape[0]
    lay = PagedLayout(cfg, B, B * -(-total // block_size),
                      block_size=block_size)
    sess = DecodeSession(cfg, params, lay)
    for b in range(B):
        lay.admit(b, total, slot=b)
        sess.prefill(b, toks[b])
        lay.ensure(b, total)
    return sess


# ---------------------------------------------------------------------------
# DecodeSession parity vs the direct model entry points
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "jamba-1.5-large-398b",
                                  "xlstm-125m"])
def test_session_step_matches_direct_lm_decode(arch):
    """session.prefill + session.step give the same tokens as calling
    lm_prefill, PagedLayout.insert_prefill and lm_decode(tables=) by
    hand."""
    cfg = _f32_cfg(arch)
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 2, 6)

    # by hand: the model entry points over a paged pool
    logits, cache = lm_prefill(params, cfg, {"tokens": jnp.asarray(toks)})
    hand = [np.asarray(jnp.argmax(logits[:, -1], axis=-1))]
    lay = PagedLayout(cfg, 2, 8, block_size=4)
    for b in range(2):
        lay.admit(b, 16, slot=b)
        lay.insert_prefill(b, jax.tree.map(lambda x: x[:, b:b + 1], cache),
                           6)
        lay.ensure(b, 16)
    cache, tables = lay.cache, jnp.asarray(lay.tables)
    for i in range(3):
        logits, cache = lm_decode(params, cfg, hand[-1][:, None], cache,
                                  jnp.full((2,), 6 + i, jnp.int32),
                                  tables=tables)
        hand.append(np.asarray(jnp.argmax(logits[:, -1], axis=-1)))

    lay = PagedLayout(cfg, 2, 8, block_size=4)
    sess = DecodeSession(cfg, params, lay)
    got = []
    for b in range(2):
        lay.admit(b, 16, slot=b)
        got.append(int(jnp.argmax(sess.prefill(b, toks[b]))))
        lay.ensure(b, 16)
    got = [np.asarray(got)]
    index = np.full((2,), 6, np.int32)
    for i in range(3):
        logits = sess.step(got[-1][:, None], index + i, width=4)
        got.append(np.asarray(jnp.argmax(logits[:, -1], axis=-1)))
    for h, g in zip(hand, got):
        assert h.tolist() == g.tolist()


def test_session_jits_only_the_paged_programs():
    """The session's jitted programs are exactly the paged four, and the
    decode step lowers under the name the chip benchmark reads its
    trace by (``jit__step_tables_fn``)."""
    jitted = {name for name, fn in vars(session_mod).items()
              if isinstance(fn, jax.stages.Wrapped)}
    assert jitted == {"_prefill_fn", "_chunk_fn", "_step_tables_fn",
                      "_draft_tables_fn"}
    cfg = _f32_cfg("qwen3-0.6b")
    params = jax.eval_shape(lambda: init_lm(cfg, KEY)[0])
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, pages=(8, 4))[0])
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
    text = session_mod._step_tables_fn.lower(
        params, cfg, i32(2, 1), cache, i32(2), i32(2, 4), None).as_text()
    assert "module @jit__step_tables_fn" in text


# ---------------------------------------------------------------------------
# K-token write/verify: multi-token step == K sequential single steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-moe-16b",
                                  "jamba-1.5-large-398b", "xlstm-125m"])
def test_k_token_step_matches_sequential(arch):
    """One step(tokens, k=K) writes the same cache state and returns
    the same per-position logits as K single-token steps — the verify
    primitive speculative decoding relies on."""
    cfg = _f32_cfg(arch)
    params, _ = init_lm(cfg, KEY)
    prompt = _prompts(cfg, 1, 6)[0]
    K = 3
    feed = _prompts(cfg, 1, K, seed=9)[0]       # arbitrary verify block

    def make_sess():
        lay = PagedLayout(cfg, 1, 12, block_size=4)
        sess = DecodeSession(cfg, params, lay)
        lay.admit("r", 6 + K + 1)
        sess.prefill("r", prompt)
        lay.ensure("r", 6 + K + 1)
        return sess

    # K sequential single-token steps
    seq = make_sess()
    seq_logits = []
    for t in range(K):
        lg = seq.step(feed[t].reshape(1, 1), np.asarray([6 + t], np.int32),
                      width=4)
        seq_logits.append(np.asarray(lg[0, 0].astype(jnp.float32)))
    # one K-token verify step
    multi = make_sess()
    lg = multi.step(feed.reshape(1, K), np.asarray([6], np.int32), width=4)
    lg = np.asarray(lg[0].astype(jnp.float32))
    for t in range(K):
        np.testing.assert_allclose(lg[t], seq_logits[t],
                                   atol=1e-4, rtol=1e-4)
    # the cache states agree too: one more step from each must match
    nxt = np.asarray([[int(np.argmax(seq_logits[-1]))]], np.int32)
    a = seq.step(nxt, np.asarray([6 + K], np.int32), width=4)
    b = multi.step(nxt, np.asarray([6 + K], np.int32), width=4)
    np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                               np.asarray(b.astype(jnp.float32)),
                               atol=1e-4, rtol=1e-4)


def test_k_token_valid_mask_freezes_tail():
    """Tokens past ``valid`` must not change the cache: a masked
    K-token step equals feeding only the valid prefix (recurrent state
    frozen, paged writes null-routed)."""
    cfg = _f32_cfg("jamba-1.5-large-398b")
    params, _ = init_lm(cfg, KEY)
    prompt = _prompts(cfg, 1, 5)[0]
    feed = _prompts(cfg, 1, 4, seed=3)[0]

    def run(tokens, valid):
        lay = PagedLayout(cfg, 1, 12, block_size=4)
        sess = DecodeSession(cfg, params, lay)
        lay.admit("r", 16)
        sess.prefill("r", prompt)
        lay.ensure("r", 5 + len(tokens))
        sess.step(tokens.reshape(1, -1), np.asarray([5], np.int32),
                  valid=None if valid is None
                  else np.asarray([valid], np.int32), width=4)
        probe = np.asarray([[7]], np.int32)
        lg = sess.step(probe, np.asarray([5 + 2], np.int32), width=4)
        return np.asarray(lg.astype(jnp.float32))

    masked = run(feed, valid=2)          # 4 fed, 2 real
    exact = run(feed[:2], valid=None)    # the 2 real ones only
    np.testing.assert_allclose(masked, exact, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# snapshot / restore round-trip (recurrent families)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "xlstm-125m"])
def test_snapshot_restore_roundtrip(arch):
    """snapshot -> K steps -> restore replays to IDENTICAL logits, and
    restore is per-row: an untouched row keeps its advanced state."""
    cfg = _f32_cfg(arch)
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 2, 6)
    sess = _prefilled(cfg, params, toks, 24)
    assert sess.layout.has_recurrent
    index = np.full((2,), 6, np.int32)

    snap = sess.snapshot()
    assert len(snap) > 0
    feed = _prompts(cfg, 2, 1, seed=4)
    first = np.asarray(sess.step(feed, index).astype(jnp.float32))
    # advance further, then roll row 0 back and replay: identical
    sess.step(feed + 1, index + 1)
    sess.restore(snap, np.asarray([True, False]))
    again = np.asarray(sess.step(feed, index,
                                 valid=np.asarray([1, 0], np.int32))
                       .astype(jnp.float32))
    np.testing.assert_allclose(again[0], first[0], atol=1e-5, rtol=1e-5)
    # row 1 was NOT restored: its recurrent state kept moving, so the
    # same probe must now answer differently
    assert not np.allclose(again[1], first[1], atol=1e-5)


def test_snapshot_is_a_copy_not_a_view():
    """Donated step buffers must never alias a snapshot: mutate the
    cache after snapshotting, the snapshot stays intact."""
    cfg = _f32_cfg("xlstm-125m")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 1, 6)
    sess = _prefilled(cfg, params, toks, 16)
    snap = sess.snapshot()
    before = [np.asarray(s) for s in snap]
    for _ in range(3):                   # donating steps mutate the pool
        sess.step(np.asarray([[5]], np.int32), np.asarray([6], np.int32))
    for b, s in zip(before, snap):
        np.testing.assert_array_equal(b, np.asarray(s))


# ---------------------------------------------------------------------------
# speculative decoding: token-identity with target-only decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [
    "qwen3-0.6b",              # dense attention
    "deepseek-moe-16b",        # attention + MoE
    "jamba-1.5-large-398b",    # hybrid mamba/attention/moe (rollback!)
])
@pytest.mark.parametrize("draft_kind", ["self", "other"])
def test_spec_decode_greedy_token_identity(arch, draft_kind):
    """Acceptance: speculative decoding at temperature 0 emits exactly
    the target-only greedy tokens — with a perfect drafter (self) and
    a disagreeing one (fresh init, near-zero accept rate)."""
    cfg = _f32_cfg(arch)
    params, _ = init_lm(cfg, KEY)
    draft = params if draft_kind == "self" \
        else init_lm(cfg, jax.random.PRNGKey(11))[0]
    toks = _prompts(cfg, 3, 12)

    def serve(dp, k):
        s = Scheduler(cfg, params, num_slots=2, max_len=28, block_size=4,
                      draft_params=dp, spec_tokens=k)
        for i in range(3):
            s.submit(Request(rid=i, prompt=toks[i, :5 + 3 * i], max_new=6))
        r = s.run(max_steps=300)
        assert len(r) == 3
        return r, s

    base, _ = serve(None, 0)
    spec, ss = serve(draft, 3)
    for i in range(3):
        assert base[i].tolist() == spec[i].tolist(), (arch, draft_kind, i)
    d = ss.stats.as_dict()
    assert d["spec_rounds"] > 0
    if draft_kind == "self":
        assert d["spec_accept_rate"] > 0.5    # only budget-tail losses
        assert d["spec_rounds"] < ss.stats.decode_tokens


def test_spec_decode_temperature_identity():
    """Sampling is deterministic in (seed, ntok), so spec decode is
    token-identical at temperature > 0 as well."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    draft, _ = init_lm(cfg, jax.random.PRNGKey(11))
    toks = _prompts(cfg, 2, 8)

    def serve(dp, k):
        s = Scheduler(cfg, params, num_slots=2, max_len=24, block_size=4,
                      draft_params=dp, spec_tokens=k)
        for i in range(2):
            s.submit(Request(rid=i, prompt=toks[i], max_new=6,
                             temperature=0.8, seed=42 + i))
        return s.run(max_steps=300)

    assert {k: v.tolist() for k, v in serve(None, 0).items()} \
        == {k: v.tolist() for k, v in serve(draft, 2).items()}


def test_spec_decode_and_eos():
    """Spec rounds with an EOS that lands inside an accepted block:
    generation stops AT the eos token."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 1, 8)

    def serve(dp, k, eos=None):
        s = Scheduler(cfg, params, num_slots=1, max_len=32, block_size=4,
                      draft_params=dp, spec_tokens=k)
        s.submit(Request(rid=0, prompt=toks[0], max_new=8, eos_id=eos))
        return s.run(max_steps=200)[0]

    gen = serve(None, 0)
    assert serve(params, 3).tolist() == gen.tolist()
    eos = int(gen[2])
    want = gen[:3].tolist()              # stops AT the eos token
    assert serve(None, 0, eos=eos).tolist() == want
    assert serve(params, 3, eos=eos).tolist() == want


# ---------------------------------------------------------------------------
# prefix pinning
# ---------------------------------------------------------------------------


def test_pin_prefix_survives_idle_and_reclaims_under_pressure():
    cfg = _f32_cfg("qwen3-0.6b")
    pool = PagedLayout(cfg, num_slots=2, num_pages=8, block_size=4,
                       pin_prefix=True)
    prompt = np.arange(11, dtype=np.int32)         # 2 full pages + tail
    pool.admit("a", 12, prompt)
    pool.ensure("a", 11)
    pool.register_prefix("a", prompt)
    pinned = pool.blocks.table("a")[:2]
    pool.release("a")                               # pool goes IDLE
    # the registered prefix pages survive: still resident + shareable
    assert all(pool.blocks.refcount(p) == 1 for p in pinned)
    assert pool.find_shared_prefix(prompt)[1] == 8
    assert pool.blocks.as_dict()["pinned_blocks"] == 2
    # a new request maps them without prefilling
    _, shared = pool.admit("b", 12, prompt)
    assert shared == 8 and pool.blocks.table("b")[:2] == pinned
    pool.release("b")
    # allocation pressure reclaims the pinned tier (oldest first) and
    # the prefix cache forgets the stolen pages
    pool.admit("big", 32)                           # all 8 pages
    pool.ensure("big", 32)
    assert pool.find_shared_prefix(prompt)[1] == 0
    assert pool.blocks.as_dict()["block_reclaims"] == 2
    pool.release("big")


def test_pin_shared_pages_not_double_counted_at_admission():
    """Mapping idle pinned pages as a shared prefix removes them from
    the reclaim tier: admission must not count them BOTH as free
    prefix pages and as reclaimable capacity (that over-promise used
    to surface as an uncaught RuntimeError from ensure() mid-serve)."""
    cfg = _f32_cfg("qwen3-0.6b")
    pool = PagedLayout(cfg, num_slots=3, num_pages=8, block_size=4,
                       pin_prefix=True)
    prompt = np.arange(16, dtype=np.int32)          # 4 full pages
    pool.admit("a", 16, prompt)
    pool.ensure("a", 16)
    pool.register_prefix("a", prompt)
    pool.release("a")                                # 4 pinned-idle pages
    pool.admit("c", 8)
    pool.ensure("c", 8)                              # 2 pages held live
    shared = pool.find_shared_prefix(prompt)
    assert shared[1] == 12                           # capped at len-1
    # 28 tokens = 7 blocks, 3 of them shared+pinned: only 2 free pages
    # remain once the shared ones stop being reclaimable -> reject
    assert not pool.can_admit(28, shared_pages=shared[0])
    with pytest.raises(RuntimeError, match="out of cache blocks"):
        pool.admit("b", 28, shared=shared)
    # a fit that honors the corrected budget still works end to end
    ok = pool.find_shared_prefix(prompt)
    slot, shared_len = pool.admit("b", 20, shared=ok)
    pool.ensure("b", 20)
    pool.release("b")
    pool.release("c")


def test_reclaim_insufficiency_leaves_pins_intact():
    """A reclaim that cannot cover the demand must raise BEFORE
    mutating: the pinned tier and the prefix cache stay consistent."""
    cfg = _f32_cfg("qwen3-0.6b")
    pool = PagedLayout(cfg, num_slots=3, num_pages=6, block_size=4,
                       pin_prefix=True)
    prompt = np.arange(9, dtype=np.int32)            # 2 full pages
    pool.admit("a", 9, prompt)
    pool.ensure("a", 9)
    pool.register_prefix("a", prompt)
    pool.release("a")                                # 2 pinned-idle, tail freed
    pinned = sorted(pool.blocks._pinned)
    # demand more than the reclaim tier holds (2 idle pinned pages)
    with pytest.raises(RuntimeError, match="out of cache blocks"):
        pool.blocks._reclaim(3)
    assert sorted(pool.blocks._pinned) == pinned     # nothing stolen
    assert pool.find_shared_prefix(prompt)[1] == 8   # prefix intact
    assert all(pool.blocks.refcount(p) == 1 for p in pinned)
    # and a coverable demand still reclaims cleanly
    pool.blocks._reclaim(2)
    assert pool.blocks.free_blocks == 6
    assert pool.find_shared_prefix(prompt)[1] == 0   # owner was told


def test_pin_prefix_unpinned_baseline_evicts():
    """Without the flag the PR-3 behavior is unchanged: last release
    evicts the prefix."""
    cfg = _f32_cfg("qwen3-0.6b")
    pool = PagedLayout(cfg, num_slots=2, num_pages=8, block_size=4)
    prompt = np.arange(11, dtype=np.int32)
    pool.admit("a", 12, prompt)
    pool.ensure("a", 11)
    pool.register_prefix("a", prompt)
    pool.release("a")
    assert pool.find_shared_prefix(prompt)[1] == 0


def test_pin_prefix_end_to_end_idle_gap():
    """Scheduler flag: a request stream with an idle gap re-serves the
    shared system prompt from pinned pages (prefix hit after the pool
    drained) and the generated tokens are unchanged."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    rng = np.random.default_rng(5)
    sys_prefix = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    mk = lambda i: np.concatenate(
        [sys_prefix, rng.integers(0, cfg.vocab_size, 3).astype(np.int32)])
    p0, p1 = mk(0), mk(1)

    def serve(pin):
        s = Scheduler(cfg, params, num_slots=2, max_len=32, block_size=4,
                      pin_prefix=pin)
        s.submit(Request(rid=0, prompt=p0, max_new=4))
        s.run(max_steps=100)             # drains: pool idle
        assert not s.active and not s.prefilling
        s.submit(Request(rid=1, prompt=p1, max_new=4))
        s.run(max_steps=100)
        return s

    cold = serve(False)
    hot = serve(True)
    assert cold.pool.prefix_hits == 0     # evicted across the gap
    assert hot.pool.prefix_hits == 1      # pinned pages survived it
    assert hot.results[1].tolist() == cold.results[1].tolist()
    assert hot.stats.prefill_tokens < cold.stats.prefill_tokens
    # hot swap drops the pins with the prefix cache
    hot.set_params(init_lm(cfg, jax.random.PRNGKey(2))[0])
    assert hot.pool.blocks.as_dict()["pinned_blocks"] == 0


# ---------------------------------------------------------------------------
# ragged gather-width split
# ---------------------------------------------------------------------------


def test_ragged_width_split_triggers_and_preserves_tokens():
    """One long request among short chats: the decode round splits into
    (narrow, wide) groups on the CPU oracle, tokens unchanged."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 3, 80)

    def serve(split):
        s = Scheduler(cfg, params, num_slots=3, max_len=96, block_size=4,
                      prefix_sharing=False)
        assert s._group_decode            # paged + attention-only + CPU
        s._group_decode = split
        s.submit(Request(rid="long", prompt=toks[0], max_new=8))
        for i in range(2):
            s.submit(Request(rid=i, prompt=toks[1 + i, :6], max_new=8))
        r = s.run(max_steps=300)
        assert len(r) == 3
        return r, s

    plain, s0 = serve(False)
    split, s1 = serve(True)
    assert s0.stats.ragged_splits == 0
    # long request: 80 tokens -> 32-wide pow2 bucket; chats sit at 4
    assert s1.stats.ragged_splits > 0
    for rid in plain:
        assert plain[rid].tolist() == split[rid].tolist(), rid
