"""Serving subsystem tests: decode-path parity, the continuous-batching
scheduler (slot reuse, EOS completion, token-budget admission,
hot-swap), the block/paged cache manager, winner export/registry, and
the ltfb -> serve CLI integration path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import replace
from repro.configs.registry import LM_ARCH_IDS, get_config
from repro.models.lm import init_lm, lm_forward
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


def _assert_greedy_parity(cfg, params, sched, reqs):
    """Every generated token must equal the argmax of a full-context
    forward over the sequence so far (prefill/decode parity)."""
    for r in reqs:
        seq = sched.full_sequence(r)
        P = r.prompt_len
        for i in range(len(sched.results[r.rid])):
            lg, _ = lm_forward(params, cfg,
                               {"tokens": jnp.asarray(seq[None, :P + i])})
            assert int(jnp.argmax(lg[0, -1])) == int(seq[P + i]), \
                (r.rid, i)


# ---------------------------------------------------------------------------
# block manager / cache pool
# ---------------------------------------------------------------------------


def test_block_manager_accounting():
    bm = BlockManager(num_blocks=8, block_size=4)
    assert blocks_for(1, 4) == 1 and blocks_for(4, 4) == 1 \
        and blocks_for(5, 4) == 2
    bm.reserve("a", 10)           # 3 blocks budgeted ...
    assert bm.used_blocks == 0 and bm.pending_blocks == 3
    bm.ensure("a", 10)            # ... and materialized
    assert bm.used_blocks == 3 and bm.free_blocks == 5
    assert bm.can_allocate(20) and not bm.can_allocate(21)
    bm.extend("a", 13)            # grow to 4 blocks
    assert bm.used_blocks == 4 and bm.high_water == 4
    with pytest.raises(ValueError):
        bm.reserve("a", 4)        # double-alloc
    with pytest.raises(RuntimeError):
        bm.reserve("b", 100)      # over budget
    assert len(bm.free("a")) == 4      # all refcounts hit zero
    assert bm.used_blocks == 0 and bm.high_water == 4
    assert bm.allocs == 4 and bm.frees == 4


def test_cache_pool_slot_lifecycle():
    cfg = _f32_cfg("qwen3-0.6b")
    pool = PagedLayout(cfg, num_slots=2, num_pages=8, block_size=4,
                       max_seq=16)
    assert pool.can_admit(16) and not pool.can_admit(17)
    s0, _ = pool.admit("r0", 12)
    s1, _ = pool.admit("r1", 12)
    pool.ensure("r0", 12)
    pool.ensure("r1", 12)
    assert {s0, s1} == {0, 1} and pool.free_slots == 0
    assert not pool.can_admit(4)            # no slot left
    pool.release("r0")
    assert pool.free_slots == 1 and pool.blocks.used_blocks == 3
    assert pool.admit("r2", 8)[0] == s0     # slot reuse
    pool.release("r1")
    pool.release("r2")
    assert pool.free_slots == 2 and pool.blocks.used_blocks == 0


# ---------------------------------------------------------------------------
# scheduler correctness
# ---------------------------------------------------------------------------


def test_scheduler_greedy_parity_and_slot_reuse():
    """5 mixed-length requests over 2 slots: all complete, every token
    matches full-context argmax, slots + pages fully recycled."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 5, 16)
    sched = Scheduler(cfg, params, num_slots=2, max_len=32, block_size=4)
    reqs = [Request(rid=i, prompt=toks[i, :4 + 3 * (i % 3)], max_new=5)
            for i in range(5)]
    for r in reqs:
        sched.submit(r)
    res = sched.run(max_steps=200)
    assert len(res) == 5
    assert sched.stats.completed == 5
    _assert_greedy_parity(cfg, params, sched, reqs)
    # everything returned to the pool
    assert sched.pool.free_slots == 2
    assert sched.pool.blocks.used_blocks == 0
    assert sched.pool.blocks.allocs == sched.pool.blocks.frees > 0
    # never more in flight than slots
    assert sched.stats.queue_depth_max >= 1


@pytest.mark.parametrize("arch", [a for a in LM_ARCH_IDS
                                  if get_config(a, smoke=True).family
                                  != "vlm"])
def test_scheduler_serves_reference_tokens(arch):
    """Every family the scheduler serves: three requests over two
    slots (so a slot is reused), each served token the argmax of a
    full-context forward over prompt + the tokens served before it."""
    cfg = _f32_cfg(arch)
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 3, 12)
    sched = Scheduler(cfg, params, num_slots=2, max_len=24, block_size=4)
    reqs = [Request(rid=i, prompt=toks[i, :5 + 3 * i], max_new=3)
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    assert len(sched.run(max_steps=200)) == 3
    _assert_greedy_parity(cfg, params, sched, reqs)


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "xlstm-125m"])
def test_scheduler_parity_recurrent_families(arch):
    """Hybrid (mamba+attn+moe) and ssm stacks decode correctly through
    the pool (exact-length prefill, per-slot write indices)."""
    cfg = _f32_cfg(arch)
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 2, 10)
    sched = Scheduler(cfg, params, num_slots=2, max_len=24, block_size=4)
    assert not sched._can_pad
    reqs = [Request(rid=i, prompt=toks[i, :6 + 3 * i], max_new=3)
            for i in range(2)]
    for r in reqs:
        sched.submit(r)
    sched.run(max_steps=100)
    _assert_greedy_parity(cfg, params, sched, reqs)


def test_scheduler_eos_frees_slot_early():
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 1, 8)
    # discover what greedy generates, then use token #2 as EOS
    probe = Scheduler(cfg, params, num_slots=1, max_len=32)
    probe.submit(Request(rid=0, prompt=toks[0], max_new=6))
    gen = probe.run(max_steps=50)[0]
    eos = int(gen[2])
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    sched.submit(Request(rid=0, prompt=toks[0], max_new=6, eos_id=eos))
    out = sched.run(max_steps=50)[0]
    assert out.tolist() == gen[:3].tolist()     # stopped AT the eos token
    assert sched.pool.free_slots == 1           # slot freed early
    assert sched.stats.decode_steps < probe.stats.decode_steps


def test_scheduler_token_budget_admission():
    """A page pool too small for two concurrent requests serializes
    them instead of failing."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    toks = _prompts(cfg, 2, 8)
    sched = Scheduler(cfg, params, num_slots=2, max_len=16, block_size=4,
                      num_blocks=3)     # 12 tokens of budget
    for i in range(2):
        sched.submit(Request(rid=i, prompt=toks[i], max_new=4))
    res = sched.run(max_steps=200)
    assert len(res) == 2 and sched.stats.completed == 2
    assert sched.pool.blocks.high_water <= 3


def test_scheduler_submit_validation():
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    sched = Scheduler(cfg, params, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(Request(rid=0, prompt=np.zeros(12, np.int32),
                             max_new=8))
    with pytest.raises(ValueError, match="seed"):
        sched.submit(Request(rid=1, prompt=np.zeros(4, np.int32),
                             max_new=4, temperature=0.7))


def test_scheduler_hot_swap_mid_stream():
    """Swapping weights between steps changes subsequent tokens without
    disturbing the in-flight cache bookkeeping."""
    cfg = _f32_cfg("qwen3-0.6b")
    p1, _ = init_lm(cfg, KEY)
    p2, _ = init_lm(cfg, jax.random.PRNGKey(7))
    toks = _prompts(cfg, 1, 8)

    def serve(swap_to=None):
        s = Scheduler(cfg, p1, num_slots=1, max_len=32)
        s.submit(Request(rid=0, prompt=toks[0], max_new=10))
        for _ in range(4):
            s.step()
        if swap_to is not None:
            s.set_params(swap_to)
        out = s.run(max_steps=100)[0]
        return s, out

    _, base = serve()
    s2, swapped = serve(p2)
    assert s2.stats.hot_swaps == 1
    n_before = 5    # 1 prefill token + 4 decode steps
    assert swapped[:n_before].tolist() == base[:n_before].tolist()
    assert swapped.tolist() != base.tolist()


# ---------------------------------------------------------------------------
# registry / winner export / integration
# ---------------------------------------------------------------------------


def _tiny_gan_population(tmp_path, rounds=1):
    """Real launch/ltfb.py run (GAN smoke) with checkpoints."""
    from repro.launch import ltfb

    ckpt_dir = str(tmp_path / "pop")
    rc = ltfb.main([
        "--arch", "icf-cyclegan", "--smoke", "--trainers", "2",
        "--rounds", str(rounds), "--steps-per-round", "1",
        "--batch", "8", "--samples", "192", "--samples-per-file", "64",
        "--num-ranks", "1", "--ckpt-dir", ckpt_dir,
        "--data-dir", str(tmp_path / "data")])
    assert rc == 0
    return ckpt_dir


def test_winner_export_and_registry(tmp_path):
    from repro.checkpoint import ckpt
    from repro.configs.icf_cyclegan import SMOKE
    from repro.models.icf_cyclegan import init_cyclegan
    from repro.serve import registry as reg

    ckpt_dir = _tiny_gan_population(tmp_path, rounds=1)
    like, _ = init_cyclegan(SMOKE, KEY)
    path, info = reg.export_winner(ckpt_dir, like)
    assert info["step"] == 1 and info["trainer"] in (0, 1)
    assert reg.latest_winner_step(ckpt_dir) == 1

    r = reg.ModelRegistry(ckpt_dir, like)
    params = r.load()
    assert r.step == 1 and not r.swaps
    assert jax.tree.structure(params) == jax.tree.structure(like)
    assert not r.refresh()                       # nothing newer

    # a newer population step appears -> auto_export picks it up
    pop = ckpt.restore_population(ckpt_dir, 1, {"params": like,
                                                "opt_state": {}})
    ckpt.save_population(ckpt_dir, 2, pop)
    r2 = reg.ModelRegistry(ckpt_dir, like, auto_export=True)
    r2.load()
    assert r2.step == 1 or r2.step == 2          # loaded something
    assert r2.refresh() is False or r2.step == 2
    assert reg.latest_winner_step(ckpt_dir) == 2


def test_serve_cli_lm_end_to_end_with_hot_swap(tmp_path, monkeypatch,
                                               capsys):
    """Acceptance: launch/serve.py loads a winner exported from a real
    launch/ltfb.py population checkpoint and hot-swaps a newer winner
    mid-stream."""
    from repro.checkpoint import ckpt
    from repro.launch import ltfb, serve
    from repro.serve import registry as reg
    from repro.serve import scheduler as sched_mod

    ckpt_dir = str(tmp_path / "pop")
    rc = ltfb.main([
        "--arch", "qwen3-0.6b", "--smoke", "--trainers", "2",
        "--rounds", "1", "--steps-per-round", "1", "--batch", "4",
        "--seq", "16", "--samples", "96", "--samples-per-file", "32",
        "--num-ranks", "1", "--ckpt-dir", ckpt_dir,
        "--data-dir", str(tmp_path / "data")])
    assert rc == 0
    assert ckpt.latest_population_step(ckpt_dir) == 1

    # drop a newer population step after scheduler step 3: the serving
    # loop (watch-every) must export + hot-swap it mid-stream
    orig_step = sched_mod.Scheduler.step
    fired = []

    def step_with_new_ckpt(self):
        if self._step_count == 3 and not fired:
            fired.append(True)
            cfg = get_config("qwen3-0.6b", smoke=True)
            like, _ = init_lm(cfg, KEY)
            pop = ckpt.restore_population(
                ckpt_dir, 1, {"params": like, "opt_state": {}})
            ckpt.save_population(ckpt_dir, 2, pop)
        orig_step(self)

    monkeypatch.setattr(sched_mod.Scheduler, "step", step_with_new_ckpt)
    rc = serve.main([
        "--arch", "qwen3-0.6b", "--smoke", "--ckpt-dir", ckpt_dir,
        "--watch-every", "2", "--requests", "6", "--slots", "2",
        "--max-new", "8", "--prompt-lens", "8,12"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "winner: step=1" in out
    assert "serving_step=2" in out           # hot-swapped mid-stream
    assert "hot_swaps=1" in out
    assert "completed=6" in out
    assert reg.latest_winner_step(ckpt_dir) == 2


def test_serve_cli_surrogate_end_to_end(tmp_path, capsys):
    """GAN winner from a real population checkpoint answers batched
    surrogate queries through the CLI."""
    from repro.launch import serve

    ckpt_dir = _tiny_gan_population(tmp_path, rounds=1)
    rc = serve.main([
        "--arch", "icf-cyclegan", "--smoke", "--ckpt-dir", ckpt_dir,
        "--queries", "5", "--query-batch", "4"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "workload=surrogate" in out
    assert "completed=5" in out


def test_surrogate_engine_matches_direct_forward():
    from repro.configs.icf_cyclegan import SMOKE
    from repro.models import icf_cyclegan as cg
    from repro.serve.surrogate import SurrogateEngine

    params, _ = cg.init_cyclegan(SMOKE, KEY)
    eng = SurrogateEngine(SMOKE, params, max_batch=16, bucket=4)
    rng = np.random.default_rng(0)
    xs = {i: rng.normal(size=(3 + i, SMOKE.input_dim)).astype(np.float32)
          for i in range(4)}
    for i, x in xs.items():
        eng.submit(i, x)
    res = eng.run(max_steps=20)
    assert eng.stats.completed == 4
    for i, x in xs.items():
        ref = np.asarray(cg.predict(params["gen"], jnp.asarray(x))
                         .astype(jnp.float32))
        np.testing.assert_allclose(res[i], ref, atol=1e-5)
