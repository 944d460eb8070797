"""Mesh-serving tests: 1-vs-8-emulated-device token parity across
layouts/families, host-0 admission broadcast determinism (follower
replay over the wire encoding), drain-mode hot swap on the mesh, the
shard_map paged-gather dispatch vs the global oracle, and the
satellite serving features (fused draft round, per-row speculative
depth, draft-arch compatibility).

Multi-device cases run in subprocesses (the in-process jax backend is
already initialized with 1 CPU device) with
``--xla_force_host_platform_device_count=8`` — the same emulation the
CI mesh job uses."""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import replace
from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve.scheduler import Request, Scheduler

KEY = jax.random.PRNGKey(0)


def _f32_cfg(arch: str):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    if cfg.moe is not None:
        cfg = replace(cfg, **{
            "moe.capacity_factor": float(cfg.moe.num_experts)})
    return cfg


def _run_mesh_script(script: str, devices: int = 8) -> None:
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": "src",
    })
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]


_PRELUDE = r"""
import dataclasses
import jax, numpy as np
from repro.configs.base import replace
from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve.scheduler import Request, Scheduler
from repro.serve.mesh import MeshScheduler, StepPlan

def f32_cfg(arch):
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              dtype="float32")
    if cfg.moe is not None:
        cfg = replace(cfg, **{
            "moe.capacity_factor": float(cfg.moe.num_experts)})
    return cfg

def trace(cfg, n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, 5 + 3 * i).astype(np.int32)
            for i in range(n)]

def serve(cls, cfg, params, prompts, layout="paged", **kw):
    s = cls(cfg, params, num_slots=4, max_len=40, block_size=4,
            layout=layout, **kw)
    for i, p in enumerate(prompts):
        s.submit(Request(rid=i, prompt=p, max_new=6))
    return s.run(max_steps=400), s
"""


# ---------------------------------------------------------------------------
# token parity: 1 device vs the 8-emulated-device mesh
# ---------------------------------------------------------------------------


def test_mesh_token_parity_attention_paged_and_dense():
    """qwen3 on a 4x2 (data, model) mesh: paged AND dense layouts are
    token-identical to the single-device scheduler on the same trace."""
    _run_mesh_script(_PRELUDE + r"""
cfg = f32_cfg("qwen3-0.6b")
params, _ = init_lm(cfg, jax.random.PRNGKey(0))
prompts = trace(cfg)
for layout in ("paged", "dense"):
    base, _ = serve(Scheduler, cfg, params, prompts, layout=layout)
    got, s = serve(MeshScheduler, cfg, params, prompts, layout=layout,
                   mesh_shape=(4, 2))
    assert s.pool.num_slots == 4 and s.data_shards == 4
    for i in base:
        assert base[i].tolist() == got[i].tolist(), (layout, i)
print("OK")
""")


def test_mesh_token_parity_hybrid():
    """jamba (mamba/attention/moe hybrid) on a 2x2 mesh: the paged
    pools shard over data, the recurrent state rows shard over data,
    tokens unchanged."""
    _run_mesh_script(_PRELUDE + r"""
cfg = f32_cfg("jamba-1.5-large-398b")
params, _ = init_lm(cfg, jax.random.PRNGKey(0))
prompts = trace(cfg, n=4)
base, _ = serve(Scheduler, cfg, params, prompts)
got, _ = serve(MeshScheduler, cfg, params, prompts, mesh_shape=(2, 2))
for i in base:
    assert base[i].tolist() == got[i].tolist(), i
print("OK")
""")


def test_mesh_spec_decode_token_identity():
    """Speculative decoding ON the mesh (fused draft, temperature > 0)
    emits exactly the single-device target-only tokens."""
    _run_mesh_script(_PRELUDE + r"""
cfg = f32_cfg("qwen3-0.6b")
params, _ = init_lm(cfg, jax.random.PRNGKey(0))
draft, _ = init_lm(cfg, jax.random.PRNGKey(7))
rng = np.random.default_rng(3)
prompts = [rng.integers(0, cfg.vocab_size, 6 + 2 * i).astype(np.int32)
           for i in range(4)]

def spec_serve(cls, dp, k, **kw):
    s = cls(cfg, params, num_slots=4, max_len=40, block_size=4,
            draft_params=dp, spec_tokens=k, **kw)
    for i, p in enumerate(prompts):
        s.submit(Request(rid=i, prompt=p, max_new=6, temperature=0.7,
                         seed=11 + i))
    return s.run(max_steps=400), s

base, _ = spec_serve(Scheduler, None, 0)
got, sm = spec_serve(MeshScheduler, draft, 3, mesh_shape=(4, 2))
for i in base:
    assert base[i].tolist() == got[i].tolist(), i
d = sm.stats.as_dict()
assert d["spec_rounds"] > 0
# fused drafting: ONE draft dispatch per verify round (replays extra)
assert d["spec_draft_steps"] == d["spec_rounds"]
print("OK")
""")


# ---------------------------------------------------------------------------
# host-0 broadcast determinism
# ---------------------------------------------------------------------------


def test_mesh_follower_replay_determinism():
    """host 0's StepPlans, round-tripped through the wire encoding,
    drive a follower replica to an IDENTICAL end state (results + pool
    accounting) — the admission-broadcast contract."""
    _run_mesh_script(_PRELUDE + r"""
cfg = f32_cfg("qwen3-0.6b")
params, _ = init_lm(cfg, jax.random.PRNGKey(0))
prompts = trace(cfg, n=4, seed=5)

def mk():
    s = MeshScheduler(cfg, params, num_slots=4, max_len=32,
                      block_size=4, mesh_shape=(4, 2))
    for i, p in enumerate(prompts):
        s.submit(Request(rid=i, prompt=p, max_new=5))
    return s

host0, follower = mk(), mk()
nadmit, steps = 0, 0
while (host0.queue or host0.active or host0.prefilling) and steps < 200:
    plan = host0.step()
    follower.step(plan=StepPlan.decode(plan.encode()))   # the wire
    nadmit += len(plan.admits)
    steps += 1
assert nadmit == 4
assert host0.results.keys() == follower.results.keys()
for k in host0.results:
    assert host0.results[k].tolist() == follower.results[k].tolist()
assert host0.pool.as_dict() == follower.pool.as_dict()
assert host0._index.tolist() == follower._index.tolist()
print("OK")
""")


def test_mesh_hot_swap_drain():
    """Drain-mode hot swap on the mesh: host 0 finds the new winner,
    the broadcast winner step swaps every replica AFTER in-flight
    requests finish on the old weights; followers load the exact
    broadcast step."""
    _run_mesh_script(_PRELUDE + r"""
import os, tempfile
from repro.checkpoint import ckpt
from repro.serve.registry import ModelRegistry

cfg = f32_cfg("qwen3-0.6b")
p1, _ = init_lm(cfg, jax.random.PRNGKey(0))
p2, _ = init_lm(cfg, jax.random.PRNGKey(7))
rng = np.random.default_rng(1)
prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
           for i in range(3)]
tmp = tempfile.mkdtemp()
ckpt.save(os.path.join(tmp, "winner_step_1.ckpt"), {"params": p1},
          metadata={"step": 1})

def mk():
    reg = ModelRegistry(tmp, p1)
    s = MeshScheduler(cfg, reg.load(), mesh_shape=(4, 2), num_slots=4,
                      max_len=32, block_size=4, registry=reg,
                      watch_every=1, swap_mode="drain")
    return s

host0, follower = mk(), mk()
for sched in (host0, follower):
    sched.submit(Request(rid=0, prompt=prompts[0], max_new=8))
    sched.submit(Request(rid=1, prompt=prompts[1], max_new=8))
plans = [host0.step() for _ in range(3)]
for p in plans:
    follower.step(plan=StepPlan.decode(p.encode()))
# the new winner appears mid-flight
ckpt.save(os.path.join(tmp, "winner_step_2.ckpt"), {"params": p2},
          metadata={"step": 2})
for sched in (host0, follower):
    sched.submit(Request(rid=2, prompt=prompts[2], max_new=4))
steps = 0
while (host0.queue or host0.active or host0.prefilling) and steps < 300:
    plan = host0.step()
    follower.step(plan=StepPlan.decode(plan.encode()))
    steps += 1
assert host0.stats.hot_swaps == 1 and follower.stats.hot_swaps == 1
assert host0.registry.step == 2 and follower.registry.step == 2
for k in host0.results:
    assert host0.results[k].tolist() == follower.results[k].tolist()

# drain semantics preserved on the mesh: in-flight rids 0/1 finished on
# the OLD weights (== a p1-only serve), rid 2 ran on the new winner
def ref_serve(params, rids_prompts, max_new):
    s = MeshScheduler(cfg, params, mesh_shape=(4, 2), num_slots=4,
                      max_len=32, block_size=4)
    for rid, p in rids_prompts:
        s.submit(Request(rid=rid, prompt=p, max_new=max_new))
    return s.run(max_steps=300)

ref = ref_serve(p1, [(0, prompts[0]), (1, prompts[1])], 8)
assert host0.results[0].tolist() == ref[0].tolist()
assert host0.results[1].tolist() == ref[1].tolist()
# rid 2 decoded alone post-drain: must equal a p2-only serve
ref2 = ref_serve(p2, [(2, prompts[2])], 4)
assert host0.results[2].tolist() == ref2[2].tolist()
print("OK")
""")


# ---------------------------------------------------------------------------
# sharded paged-gather dispatch vs the global oracle
# ---------------------------------------------------------------------------


def test_sharded_paged_gather_matches_oracle():
    """ops.paged_attention under a (data, model) sharding context ==
    ref.paged_attention_ref on the unsharded global pool, for K = 1 and
    a K = 3 verify staircase, GQA heads, inside scan-under-jit with the
    scanned layer index picking a layer of the stacked pool — and no
    page moves across `data` (each row's tables stay in its shard)."""
    _run_mesh_script(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels import ops, ref
from repro.parallel.sharding import use_sharding

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
D_, bs, Hkv, H, hd = 4, 4, 2, 4, 8
pps = 3
P_tot = D_ * (pps + 1)
B, W = 8, 3
rng = np.random.default_rng(0)
layers, layer = 2, 1                  # a stacked pool, its second layer
k_pages = rng.normal(size=(layers, P_tot, bs, Hkv, hd)).astype(np.float32)
v_pages = rng.normal(size=(layers, P_tot, bs, Hkv, hd)).astype(np.float32)
tables = np.zeros((B, W), np.int32)
lengths = np.zeros((B,), np.int32)
for b in range(B):
    s = b // (B // D_)
    base = s * (pps + 1)
    tables[b] = [base + (b % pps), base + ((b + 1) % pps),
                 base + pps]                      # 2 real pages + null
    lengths[b] = 1 + b % (2 * bs - 3)
kp = jax.device_put(jnp.asarray(k_pages),
                    NamedSharding(mesh, P(None, "data", None, "model", None)))
vp = jax.device_put(jnp.asarray(v_pages),
                    NamedSharding(mesh, P(None, "data", None, "model", None)))
for K in (1, 3):
    q = rng.normal(size=(B, K, H, hd)).astype(np.float32)
    if K == 1:
        qq = q[:, 0]
    else:
        qq = q
    want = ref.paged_attention_ref(
        jnp.asarray(qq), jnp.asarray(k_pages), jnp.asarray(v_pages), layer,
        jnp.asarray(tables), jnp.asarray(lengths))

    def f(q, kp, vp, t, l):
        def body(c, li):
            o = ops.paged_attention(q, kp, vp, li, t, l)
            return c, o
        _, os_ = jax.lax.scan(body, 0.0, jnp.arange(2))
        return os_[layer]

    with use_sharding(mesh):
        got = jax.jit(f)(jnp.asarray(qq), kp, vp, jnp.asarray(tables),
                         jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
print("OK")
""")


def test_engine_mesh_generate_parity():
    """Engine.generate over the mesh == Engine.generate single-device
    (the dry-run decode cell's weights-stationary layout, live)."""
    _run_mesh_script(r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve.engine import Engine
from repro.serve.mesh import make_serve_mesh

cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                          dtype="float32")
params, _ = init_lm(cfg, jax.random.PRNGKey(0))
toks = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab_size, (8, 6)).astype(np.int32))
base = Engine(cfg, params, max_len=32).generate(toks, 5)
mesh = make_serve_mesh(4, 2)
got = Engine(cfg, params, max_len=32, mesh=mesh).generate(toks, 5)
assert np.asarray(base).tolist() == np.asarray(got).tolist()
print("OK")
""")


# ---------------------------------------------------------------------------
# satellites: fused draft round, per-row depth, draft compatibility
# ---------------------------------------------------------------------------


def _spec_serve(cfg, params, prompts, draft=None, k=0, **kw):
    s = Scheduler(cfg, params, num_slots=2, max_len=32, block_size=4,
                  draft_params=draft, spec_tokens=k, **kw)
    for i, p in enumerate(prompts):
        s.submit(Request(rid=i, prompt=p, max_new=6))
    return s.run(max_steps=300), s


def test_fused_draft_round_is_two_dispatches():
    """The fused draft step collapses a round from K+1 draft dispatches
    to ONE (plus the verify): tokens identical either way, on dense and
    hybrid (rollback) stacks."""
    for arch in ("qwen3-0.6b", "jamba-1.5-large-398b"):
        cfg = _f32_cfg(arch)
        params, _ = init_lm(cfg, KEY)
        prompts = [_p for _p in np.random.default_rng(2).integers(
            0, cfg.vocab_size, (2, 7)).astype(np.int32)]
        base, _ = _spec_serve(cfg, params, prompts)
        fused, sf = _spec_serve(cfg, params, prompts, draft=params, k=3)
        seq, ss = _spec_serve(cfg, params, prompts, draft=params, k=3,
                              spec_fused=False)
        for i in base:
            assert base[i].tolist() == fused[i].tolist(), (arch, i)
            assert base[i].tolist() == seq[i].tolist(), (arch, i)
        df, ds = sf.stats.as_dict(), ss.stats.as_dict()
        assert df["spec_rounds"] == ds["spec_rounds"]
        # fused: one draft dispatch per round; sequential: K+1 per round
        assert df["spec_draft_steps"] == df["spec_rounds"]
        assert ds["spec_draft_steps"] > 3 * ds["spec_rounds"]


def test_fused_draft_temperature_identity_with_divergent_drafter():
    """At temperature > 0 the host resample can diverge from the
    on-device greedy feed — the drafter-cache repair keeps the output
    token-identical to target-only decoding."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    draft, _ = init_lm(cfg, jax.random.PRNGKey(11))
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size), np.int32)

    def serve(dp, k):
        s = Scheduler(cfg, params, num_slots=2, max_len=28, block_size=4,
                      draft_params=dp, spec_tokens=k)
        for i in range(2):
            s.submit(Request(rid=i, prompt=toks[i], max_new=6,
                             temperature=0.9, seed=42 + i))
        return s.run(max_steps=300)

    assert {k: v.tolist() for k, v in serve(None, 0).items()} \
        == {k: v.tolist() for k, v in serve(draft, 3).items()}


def test_spec_adapt_per_row_depth():
    """--spec-adapt: a disagreeing drafter drives a row's K down to 1,
    a perfect (self) drafter keeps it at the cap; tokens stay identical
    to target-only decoding either way."""
    cfg = _f32_cfg("qwen3-0.6b")
    params, _ = init_lm(cfg, KEY)
    bad_draft, _ = init_lm(cfg, jax.random.PRNGKey(11))
    prompts = [p for p in np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 6)).astype(np.int32)]

    base, _ = _spec_serve(cfg, params, prompts)
    good, sg = _spec_serve(cfg, params, prompts, draft=params, k=4,
                           spec_adapt=True)
    bad, sb = _spec_serve(cfg, params, prompts, draft=bad_draft, k=4,
                          spec_adapt=True)
    for i in base:
        assert base[i].tolist() == good[i].tolist(), i
        assert base[i].tolist() == bad[i].tolist(), i
    assert set(sg.spec_k_by_rid) == {0, 1}
    assert set(sb.spec_k_by_rid) == {0, 1}
    # near-zero accept: every row's depth collapses toward 1
    assert all(k <= 2 for k in sb.spec_k_by_rid.values())
    assert sb.stats.as_dict()["spec_k_mean"] \
        < sg.stats.as_dict()["spec_k_mean"]
    # a perfect drafter holds (or regrows to) the cap
    assert max(sg.spec_k_by_rid.values()) >= 3


def test_draft_compat_vocab_mismatch_is_a_clear_error():
    """A drafter with a different vocab must fail LOUDLY, at setup."""
    from repro.serve.registry import check_draft_compat, load_draft

    cfg = _f32_cfg("qwen3-0.6b")
    bad = dataclasses.replace(cfg, vocab_size=cfg.vocab_size * 2)
    with pytest.raises(ValueError, match="tokenizer"):
        check_draft_compat(cfg, bad)
    params, _ = init_lm(cfg, KEY)
    with pytest.raises(ValueError, match="tokenizer"):
        Scheduler(cfg, params, num_slots=1, max_len=16,
                  draft_params=params, spec_tokens=2, draft_cfg=bad)
    # load-time check: a checkpoint whose embedding disagrees with the
    # target's vocab is rejected with a clear message
    import tempfile

    from repro.checkpoint import ckpt
    small, _ = init_lm(bad, KEY)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "draft.ckpt")
        ckpt.save(path, {"params": small}, metadata={})
        with pytest.raises(ValueError, match="tokenizer-incompatible"):
            load_draft(path, small, expect_vocab=cfg.vocab_size)


def test_draft_arch_smaller_model_serves():
    """Per-session configs: a drafter with FEWER layers/heads than the
    target proposes tokens through its own pool; output still token-
    identical to target-only decoding."""
    cfg = _f32_cfg("qwen3-0.6b")
    small = dataclasses.replace(cfg, num_layers=1, name="qwen3-draft")
    params, _ = init_lm(cfg, KEY)
    dparams, _ = init_lm(small, jax.random.PRNGKey(3))
    prompts = [p for p in np.random.default_rng(6).integers(
        0, cfg.vocab_size, (2, 6)).astype(np.int32)]
    base, _ = _spec_serve(cfg, params, prompts)
    spec, ss = _spec_serve(cfg, params, prompts, draft=dparams, k=3,
                           draft_cfg=small)
    for i in base:
        assert base[i].tolist() == spec[i].tolist(), i
    assert ss.stats.as_dict()["spec_rounds"] > 0


def test_parse_mesh_specs():
    from repro.serve.mesh import parse_mesh
    assert parse_mesh("4,2") == (4, 2)
    assert parse_mesh("8") == (8, 1)
    assert parse_mesh("data=2,model=4") == (2, 4)
    with pytest.raises(ValueError):
        parse_mesh("1,2,3")


def test_serve_cache_specs_resolve_mesh_placement():
    """serve_cache_specs + the serve rules resolve every cache leaf's
    mesh placement WITHOUT allocating: paged pools shard their page dim
    over `data`, recurrent state rows shard their batch dim over
    `data` — the layout the live mesh places the real pools with."""
    _run_mesh_script(r"""
import jax, numpy as np
from jax.sharding import Mesh
from repro.configs.registry import get_config
from repro.launch.specs import serve_cache_specs
from repro.parallel.sharding import tree_shardings
from repro.serve.mesh import MESH_SERVE_RULES

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
for arch in ("qwen3-0.6b", "jamba-1.5-large-398b"):
    cfg = get_config(arch, smoke=True)
    shapes, axes = serve_cache_specs(cfg, num_slots=8, num_pages=15,
                                     block_size=4)
    sh = tree_shardings(mesh, axes, shapes, **MESH_SERVE_RULES)
    leaves = list(zip(jax.tree.leaves(shapes), jax.tree.leaves(sh)))
    assert leaves
    data_sharded = 0
    for sds, spec in leaves:
        ss = spec.shard_shape(sds.shape)
        assert all(a % b == 0 for a, b in zip(sds.shape, ss))
        if ss != sds.shape:
            data_sharded += 1
    assert data_sharded > 0, arch      # something actually sharded
print("OK")
""")
