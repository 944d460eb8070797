"""Paged-attention serving tests: kernel-vs-oracle, chunked-prefill
equivalence, lazy page-overflow allocation, prefix-sharing refcounts,
and drain-aware hot swap."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import replace
from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve.kv_cache import BlockManager, PagedLayout, blocks_for
from repro.serve.scheduler import Request, Scheduler

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


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,D,bs,W,lengths,block_pages", [
    pytest.param(2, 4, 4, 32, 4, 3, None, 16, id="2-4-4-32-4-3"),   # MHA
    pytest.param(3, 8, 2, 32, 8, 2, None, 16, id="3-8-2-32-8-2"),   # GQA 4:1
    pytest.param(2, 4, 1, 64, 4, 4, None, 16, id="2-4-1-64-4-4"),   # MQA
    # an idle row (length 0) between live ones: zeros from both
    pytest.param(3, 4, 2, 32, 4, 6, (9, 0, 17), 2, id="idle-row"),
    # 26 tokens over blocks of 2 pages (8 tokens): four blocks, the last
    # one part-way filled
    pytest.param(2, 4, 2, 32, 4, 7, (26, 3), 2, id="multi-block"),
    # W = 7 is no multiple of block_pages = 3: the last block is short
    pytest.param(2, 4, 2, 32, 4, 7, (24, 11), 3, id="ragged-width"),
    # length 6: tokens 6..9 of a 4-token verify cross the 8-token block
    # boundary at 8
    pytest.param(2, 8, 2, 32, 4, 5, (6, 14), 2, id="staircase-block"),
    pytest.param(2, 4, 2, 32, 4, 5, (13, 4), 1, id="one-page-blocks"),
    pytest.param(2, 4, 2, 32, 4, 3, (9, 5), 8, id="blocks-past-width"),
])
@pytest.mark.parametrize("K", [1, 3, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_attention_kernel_matches_ref(B, H, Hkv, D, bs, W, lengths,
                                            block_pages, K, dtype, layer):
    """Pallas gather-decode/verify kernel (interpret) == jnp oracle
    over scattered pages of one layer of a stacked pool, null-page rows
    included; K > 1 exercises the speculative-verify staircase (query t
    reaches lengths + t).  Every layer of the pool holds other values,
    so a wrong or ignored layer index reads the wrong pages.  Rows of
    length 0 are idle: both return zeros for them.  The kernel walks a
    row's pages ``block_pages`` at a time."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    P, layers = 9, 3           # pool pages (+1 null), stacked layers
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, K, H, D), dtype)
    if K == 1:                 # exercise the 3D single-token surface
        q = q[:, 0]
    kp = jax.random.normal(ks[1], (layers, P + 1, bs, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (layers, P + 1, bs, Hkv, D), dtype)
    rng = np.random.default_rng(0)
    # scattered, non-contiguous tables; trailing entries null
    tables = rng.permutation(max(P, B * W))[:B * W].reshape(B, W) % P
    tables = tables.astype(np.int32)
    if lengths is None:
        lengths = rng.integers(1, W * bs - K + 2, size=(B,))
    lengths = np.minimum(np.asarray(lengths, np.int32), W * bs - K + 1)
    for b in range(B):
        used = blocks_for(int(lengths[b]) + K - 1, bs) if lengths[b] else 0
        tables[b, used:] = P    # null page
    args = (jnp.asarray(tables), jnp.asarray(lengths))
    out = paged_attention(q, kp, vp, jnp.int32(layer), *args,
                          block_pages=block_pages, interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.int32(layer), *args)
    assert out.shape == q.shape
    assert not np.isnan(np.asarray(ref, np.float32)).any()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
    idle = lengths == 0
    assert not np.asarray(out, np.float32)[idle].any()
    # the oracle on the layer alone (a one-layer stack) agrees, and
    # another layer's pages give another answer
    alone = paged_attention_ref(q, kp[layer:layer + 1], vp[layer:layer + 1],
                                0, *args)
    np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                  np.asarray(ref, np.float32))
    other = paged_attention(q, kp, vp, jnp.int32(1), *args,
                            block_pages=block_pages, interpret=True)
    assert not np.allclose(np.asarray(other, np.float32),
                           np.asarray(out, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("K", [1, 4], ids=["decode", "verify"])
def test_paged_attention_grid_has_no_table_width(K):
    """The kernel's grid is one step per row: a table-width (page)
    dimension in it would bring back a pipeline step per page of every
    slot, live or idle.  Read from the traced jaxpr, at a table width
    (24) unlike the batch (4) and every other size."""
    from repro.kernels.paged_attention import paged_attention

    B, H, Hkv, D, bs, W = 4, 8, 2, 128, 16, 24
    S = jax.ShapeDtypeStruct
    pool = S((2, 65, bs, Hkv, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, t, n: paged_attention(q, k, v, 1, t, n))(
            S((B, K, H, D), jnp.bfloat16), pool, pool,
            S((B, W), jnp.int32), S((B,), jnp.int32))
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].params["grid_mapping"].grid) == (B,)


@pytest.mark.parametrize("mode", ["decode", "chunk"])
def test_paged_write_touches_only_its_layer(mode):
    """A paged decode step and a prefill chunk write their rows into
    layer ``l`` of the stacked pool in place: every other layer's pages
    stay bitwise equal, and so does every page of layer ``l`` they do
    not write."""
    from repro.models import layers as Lyr

    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    mixer = jax.tree.map(lambda a: a[0], params["body"][0]["mixer"])
    P, bs, n_layers, layer = 8, 4, 3, 1
    pool, _ = Lyr.init_paged_attention_pool(cfg, n_layers, P, bs)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    pool = {n: jax.random.normal(k, pool[n].shape, pool[n].dtype)
            for n, k in zip(("k", "v"), ks)}
    if mode == "decode":
        # rows at positions 5 and 2 (pages 3 and 6), row 2 idle
        tables = jnp.asarray([[0, 3, 8], [6, 8, 8], [8, 8, 8]], jnp.int32)
        index = jnp.asarray([5, 2, -1], jnp.int32)
        x = jax.random.normal(KEY, (3, 1, cfg.d_model), jnp.float32)
        _, new = Lyr.attention_decode_paged(
            mixer, cfg, x, pool, jnp.int32(layer), index,
            jnp.maximum(index, 0)[:, None], tables)
        written = {(3, 1), (6, 2), (P, 0)}       # the idle row: null page
    else:
        # prompt positions 4..9 of a 7-token prompt: 7..9 are padding
        tables = jnp.asarray([[2, 5, 7]], jnp.int32)
        x = jax.random.normal(KEY, (1, 6, cfg.d_model), jnp.float32)
        pos = 4 + jnp.arange(6, dtype=jnp.int32)[None]
        _, new = Lyr.attention_chunk_paged(
            mixer, cfg, x, pool, jnp.int32(layer), tables, jnp.int32(4),
            jnp.int32(7), pos)
        written = {(5, 0), (5, 1), (5, 2), (P, 3), (P, 0), (P, 1)}
    for n in ("k", "v"):
        old, got = np.asarray(pool[n]), np.asarray(new[n])
        assert got.shape == old.shape
        for li in range(n_layers):
            if li != layer:
                np.testing.assert_array_equal(got[li], old[li])
        same = np.ones((P + 1, bs), bool)
        for page, off in written:
            same[page, off] = False
        np.testing.assert_array_equal(got[layer][same], old[layer][same])
        assert not np.array_equal(got[layer], old[layer])


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_one_shot():
    """Chunked prefill (prefill_chunk=4) produces exactly the one-shot
    tokens; the chunk counter proves slices actually ran."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 2, 14)

    def serve(chunk):
        s = Scheduler(cfg, params, num_slots=2, max_len=32, block_size=4,
                      prefill_chunk=chunk)
        for i in range(2):
            s.submit(Request(rid=i, prompt=toks[i, :9 + 4 * i], max_new=4))
        r = s.run(max_steps=200)
        assert len(r) == 2
        return r, s

    one, s1 = serve(0)
    chunked, s2 = serve(4)
    assert s2.stats.prefill_chunks > s1.stats.prefill_chunks
    assert s2.stats.prefill_chunks >= 3    # 9 and 13 tokens in 4-chunks
    for i in range(2):
        assert one[i].tolist() == chunked[i].tolist(), i


# ---------------------------------------------------------------------------
# lazy page allocation / overflow
# ---------------------------------------------------------------------------


def test_block_manager_lazy_reservation():
    bm = BlockManager(num_blocks=8, block_size=4)
    bm.reserve("a", 20)                    # 5 blocks budgeted, 0 claimed
    assert bm.used_blocks == 0 and bm.pending_blocks == 5
    assert bm.available_blocks == 3
    assert bm.can_allocate(12) and not bm.can_allocate(13)
    got = bm.ensure("a", 6)                # materialize 2 pages
    assert len(got) == 2 and bm.used_blocks == 2 and bm.pending_blocks == 3
    assert bm.ensure("a", 6) == []         # idempotent
    with pytest.raises(RuntimeError, match="overflows"):
        bm.ensure("a", 24)                 # beyond the 5-block budget
    bm.extend("a", 24)                     # growing the budget is fine
    assert bm.used_blocks == 6
    released = bm.free("a")
    assert len(released) == 6 and bm.used_blocks == 0
    assert bm.pending_blocks == 0 and bm.available_blocks == 8


def test_page_overflow_allocation_during_decode():
    """Decode crossing a page boundary claims its next page lazily; an
    EOS-early request never touches the tail of its reservation."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 1, 6)
    sched = Scheduler(cfg, params, num_slots=1, max_len=32, block_size=4)
    # 6 prompt + 10 new = 16 tokens -> 4 pages reserved; prompt = 2
    sched.submit(Request(rid=0, prompt=toks[0], max_new=10))
    sched.step()                             # admit + prefill
    bm = sched.pool.blocks
    after_prefill = bm.used_blocks
    assert after_prefill == blocks_for(6, 4) == 2
    assert bm.pending_blocks == 2            # rest of the budget, unclaimed
    sched.run(max_steps=100)
    assert bm.allocs == 4                    # pages materialized one by one
    assert bm.used_blocks == 0               # all recycled

    # EOS-early: same request shape, stop after 2 generated tokens
    probe = Scheduler(cfg, params, num_slots=1, max_len=32, block_size=4)
    probe.submit(Request(rid=0, prompt=toks[0], max_new=10))
    gen = probe.run(max_steps=100)[0]
    eos = int(gen[1])
    s2 = Scheduler(cfg, params, num_slots=1, max_len=32, block_size=4)
    s2.submit(Request(rid=0, prompt=toks[0], max_new=10, eos_id=eos))
    s2.run(max_steps=100)
    assert s2.pool.blocks.allocs < 4         # tail pages never claimed


# ---------------------------------------------------------------------------
# prefix sharing refcounts
# ---------------------------------------------------------------------------


def test_prefix_sharing_refcounts_pool_level():
    cfg = _f32_cfg("qwen3-0.6b")
    pool = PagedLayout(cfg, num_slots=3, num_pages=12, block_size=4)
    prompt = np.arange(11, dtype=np.int32)          # 2 full pages + tail
    _, shared = pool.admit("a", 16, prompt)
    assert shared == 0                               # nothing cached yet
    pool.ensure("a", 11)
    pool.register_prefix("a", prompt)
    a_pages = pool.blocks.table("a")[:2]

    # same prompt again -> both full pages mapped, refcount 2
    _, shared_b = pool.admit("b", 16, prompt)
    assert shared_b == 8
    assert pool.blocks.table("b")[:2] == a_pages
    assert all(pool.blocks.refcount(p) == 2 for p in a_pages)
    assert pool.prefix_hits == 1 and pool.prefix_shared_tokens == 8

    # a longer prompt sharing only the prefix chain
    prompt_c = np.concatenate([prompt[:8], np.arange(50, 58,
                                                     dtype=np.int32)])
    _, shared_c = pool.admit("c", 20, prompt_c.astype(np.int32))
    assert shared_c == 8
    assert all(pool.blocks.refcount(p) == 3 for p in a_pages)

    # the original owner dies first: shared pages must survive
    pool.release("a")
    assert all(pool.blocks.refcount(p) == 2 for p in a_pages)
    assert pool.find_shared_prefix(prompt)[1] == 8   # still resident
    pool.release("b")
    pool.release("c")
    assert all(pool.blocks.refcount(p) == 0 for p in a_pages)
    assert pool.blocks.used_blocks == 0
    assert pool.find_shared_prefix(prompt)[1] == 0   # evicted


def test_prefix_sharing_end_to_end_parity():
    """Requests sharing a system prefix decode the same tokens as
    fully-isolated requests, and the shared pages skip prefill work."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    rng = np.random.default_rng(5)
    sys_prefix = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate([sys_prefix, rng.integers(
        0, cfg.vocab_size, 3 + i).astype(np.int32)]) for i in range(3)]

    def serve(sharing):
        s = Scheduler(cfg, params, num_slots=3, max_len=32, block_size=4,
                      prefix_sharing=sharing)
        for i, p in enumerate(prompts):
            s.submit(Request(rid=i, prompt=p, max_new=4))
        r = s.run(max_steps=200)
        assert len(r) == 3
        return r, s

    iso, s_iso = serve(False)
    shr, s_shr = serve(True)
    assert s_iso.pool.prefix_hits == 0
    assert s_shr.pool.prefix_hits >= 1
    assert s_shr.pool.prefix_shared_tokens >= 8
    assert s_shr.stats.prefill_tokens < s_iso.stats.prefill_tokens
    for i in range(3):
        assert iso[i].tolist() == shr[i].tolist(), i


# ---------------------------------------------------------------------------
# drain-aware hot swap
# ---------------------------------------------------------------------------


class _ArmedRegistry:
    """refresh() reports a new winner exactly once, when armed."""

    def __init__(self):
        self.params = None
        self.armed_params = None

    def refresh(self):
        if self.armed_params is not None:
            self.params = self.armed_params
            self.armed_params = None
            return True
        return False


def test_hot_swap_invalidates_prefix_cache():
    """An immediate-mode weight swap must flush the prefix cache: a
    post-swap request with the same prompt may not attend over KV pages
    computed under the old weights."""
    cfg = _f32_cfg("qwen3-0.6b")
    p1, _ = init_lm(cfg, KEY)
    p2, _ = init_lm(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)

    sched = Scheduler(cfg, p1, num_slots=2, max_len=32, block_size=4)
    sched.submit(Request(rid="a", prompt=prompt, max_new=8))
    for _ in range(3):
        sched.step()        # "a" prefilled + registered, still decoding
    assert sched.pool.find_shared_prefix(prompt)[1] == 8
    sched.set_params(p2)
    assert sched.pool.find_shared_prefix(prompt)[1] == 0   # flushed
    sched.submit(Request(rid="b", prompt=prompt, max_new=4))
    out = sched.run(max_steps=200)
    assert sched.pool.prefix_hits == 0     # "b" never mapped old pages

    # "b"'s tokens must equal a fresh p2-only serve of the same prompt
    ref = Scheduler(cfg, p2, num_slots=1, max_len=32, block_size=4)
    ref.submit(Request(rid=0, prompt=prompt, max_new=4))
    assert out["b"].tolist() == ref.run(max_steps=100)[0].tolist()


def test_drain_swap_finishes_in_flight_on_old_weights():
    cfg = _f32_cfg("qwen3-0.6b")
    p1, _ = init_lm(cfg, KEY)
    p2, _ = init_lm(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]

    def serve(swap_to, mode):
        reg = _ArmedRegistry()
        s = Scheduler(cfg, p1, num_slots=2, max_len=32, block_size=4,
                      registry=reg, watch_every=1, swap_mode=mode)
        s.submit(Request(rid=0, prompt=prompts[0], max_new=8))
        s.submit(Request(rid=1, prompt=prompts[1], max_new=8))
        for _ in range(3):
            s.step()
        reg.armed_params = swap_to
        if swap_to is not None:
            assert not s.draining
        s.submit(Request(rid=2, prompt=prompts[2], max_new=4))
        return s.run(max_steps=200), s

    base, _ = serve(None, "drain")
    drain, sd = serve(p2, "drain")
    imm, si = serve(p2, "immediate")
    # drain: in-flight requests 0/1 finish on the OLD weights
    assert drain[0].tolist() == base[0].tolist()
    assert drain[1].tolist() == base[1].tolist()
    # immediate: weights change under request 0 mid-stream
    assert imm[0].tolist() != base[0].tolist()
    # both modes: the late admission runs on the NEW weights
    assert drain[2].tolist() != base[2].tolist()
    assert drain[2].tolist() == imm[2].tolist()
    assert sd.stats.hot_swaps == 1 and si.stats.hot_swaps == 1
    assert not sd.draining


# ---------------------------------------------------------------------------
# surrogate staging/compute overlap
# ---------------------------------------------------------------------------


def test_surrogate_pipeline_overlaps_staging():
    """The double-buffered engine stages batch N+1 while batch N's
    device compute is in flight, without changing any result."""
    from repro.configs.icf_cyclegan import SMOKE
    from repro.models import icf_cyclegan as cg
    from repro.serve.surrogate import SurrogateEngine

    params, _ = cg.init_cyclegan(SMOKE, KEY)
    eng = SurrogateEngine(SMOKE, params, max_batch=8, bucket=4)
    rng = np.random.default_rng(0)
    xs = {i: rng.normal(size=(6, SMOKE.input_dim)).astype(np.float32)
          for i in range(5)}
    for i, x in xs.items():
        eng.submit(i, x)
    res = eng.run(max_steps=50)
    assert eng.stats.completed == 5
    assert eng.overlapped_stages >= 3   # 5 one-query batches, pipelined
    for i, x in xs.items():
        ref = np.asarray(cg.predict(params["gen"], jnp.asarray(x))
                         .astype(jnp.float32))
        np.testing.assert_allclose(res[i], ref, atol=1e-5)
