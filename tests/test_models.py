"""Per-architecture smoke tests (deliverable f) + cross-path parity.

Every assigned architecture instantiates its REDUCED config, runs one
forward/train step on CPU, and asserts output shapes + no NaNs.  The
parity tests prove prefill + paged decode == full forward for every
family (the strongest correctness property of the serving path).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MeshConfig, OptimizerConfig, replace
from repro.configs.registry import LM_ARCH_IDS, get_config
from repro.data.tokens import train_batch
from repro.models.lm import init_lm, lm_decode, lm_forward, lm_prefill
from repro.serve.kv_cache import PagedLayout
from repro.train.steps import init_lm_state, make_lm_train_step

KEY = jax.random.PRNGKey(0)


def _smoke_batch(cfg, B=2, S=16):
    b = train_batch(cfg, B, S, seed=0)
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.mark.parametrize("arch", LM_ARCH_IDS)
def test_arch_smoke_forward_and_shapes(arch):
    cfg = get_config(arch, smoke=True)
    params, axes = init_lm(cfg, KEY)
    batch = _smoke_batch(cfg)
    logits, aux = lm_forward(params, cfg, batch)
    B = batch["labels"].shape[0]
    S = batch["labels"].shape[1]
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # axes tree mirrors params tree
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda t: isinstance(t, tuple)
        and all(x is None or isinstance(x, str) for x in t))


@pytest.mark.parametrize("arch", LM_ARCH_IDS)
def test_arch_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1)
    state, _ = init_lm_state(cfg, opt_cfg, KEY)
    step = jax.jit(make_lm_train_step(cfg, opt_cfg, MeshConfig(remat="full")))
    batch = _smoke_batch(cfg)
    l0 = None
    for i in range(3):
        state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"])), (arch, i)
        l0 = l0 or float(metrics["loss"])
    assert float(metrics["loss"]) < l0 + 1.0  # no explosion


@pytest.mark.parametrize("arch", LM_ARCH_IDS)
def test_prefill_decode_parity(arch):
    """prefill(prompt) + decode steps == full forward, per family: the
    prompt's cache is scattered into a page pool
    (``PagedLayout.insert_prefill``) and every later token is decoded
    through the block tables."""
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, dtype="float32")
    if cfg.moe is not None:   # dropless so train-mode forward matches
        cfg = replace(cfg, **{
            "moe.capacity_factor": float(cfg.moe.num_experts)})
    params, _ = init_lm(cfg, KEY)
    B, S, Sp = 2, 12, 8
    if cfg.family == "vlm":
        batch = _smoke_batch(cfg, B, S)
        logits_full, _ = lm_forward(params, cfg, batch)
        pre = {k: (v[:, :, :Sp] if k == "positions" else v[:, :Sp])
               for k, v in batch.items() if k != "labels"}
        lg_pre, _ = lm_prefill(params, cfg, pre)
        np.testing.assert_allclose(
            np.asarray(lg_pre[:, -1]), np.asarray(logits_full[:, Sp - 1]),
            atol=1e-4)
        return
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    logits_full, _ = lm_forward(params, cfg, {"tokens": toks})
    lg_pre, cache0 = lm_prefill(params, cfg, {"tokens": toks[:, :Sp]})
    lay = PagedLayout(cfg, num_slots=B, num_pages=2 * B * S // 4,
                      block_size=4)
    for b in range(B):
        lay.admit(b, S, slot=b)
        lay.insert_prefill(b, jax.tree.map(lambda x: x[:, b:b + 1], cache0),
                           Sp)
        lay.ensure(b, S)
    cache, tables = lay.cache, jnp.asarray(lay.tables)
    errs = [float(jnp.max(jnp.abs(lg_pre[:, -1] - logits_full[:, Sp - 1])))]
    for i in range(Sp, S):
        lg, cache = lm_decode(params, cfg, toks[:, i:i + 1], cache,
                              jnp.full((B,), i, jnp.int32), tables=tables)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, i]))))
    assert max(errs) < 1e-3, (arch, errs)


def test_mamba_chunked_matches_sequential():
    from repro.models.layers import KeyGen
    from repro.models.ssm import (init_mamba, mamba_block,
                                  mamba_ref_sequential)
    cfg = dataclasses.replace(
        get_config("jamba-1.5-large-398b", smoke=True), dtype="float32")
    p, _ = init_mamba(KeyGen(KEY), cfg)
    x = jax.random.normal(KEY, (2, 37, cfg.d_model), jnp.float32) * 0.5
    np.testing.assert_allclose(
        np.asarray(mamba_block(p, cfg, x)),
        np.asarray(mamba_ref_sequential(p, cfg, x)), atol=1e-4)


def test_mlstm_chunkwise_matches_recurrence():
    from repro.models.layers import KeyGen
    from repro.models.xlstm import (init_mlstm, init_mlstm_state,
                                    mlstm_block, mlstm_decode)
    cfg = dataclasses.replace(
        get_config("xlstm-125m", smoke=True), dtype="float32")
    p, _ = init_mlstm(KeyGen(KEY), cfg)
    x = jax.random.normal(KEY, (2, 33, cfg.d_model), jnp.float32) * 0.5
    y_chunk = mlstm_block(p, cfg, x)
    state, _ = init_mlstm_state(cfg, 2)
    ys = []
    for t in range(x.shape[1]):
        y, state = mlstm_decode(p, cfg, x[:, t:t + 1], state)
        ys.append(y)
    y_rec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_rec),
                               atol=1e-4)


def test_param_count_matches_literature():
    """Total/active parameter counts are within 15% of the published
    sizes (validates the MODEL_FLOPS roofline inputs)."""
    expect = {
        "phi3.5-moe-42b-a6.6b": (42e9, 6.6e9),
        "deepseek-moe-16b": (16.4e9, 2.8e9),
        "codeqwen1.5-7b": (7.3e9, 7.3e9),
        "granite-8b": (8.1e9, 8.1e9),
        "jamba-1.5-large-398b": (398e9, 94e9),
        # musicgen uses a 2-matrix GELU FFN upstream; this framework's
        # uniform SwiGLU block adds one d_model x d_ff matrix per layer
        # (+0.3B) — documented adaptation, MODEL_FLOPS uses our count.
        "musicgen-medium": (1.82e9, 1.82e9),
        "qwen2-vl-7b": (7.6e9, 7.6e9),
        "qwen2.5-3b": (3.1e9, 3.1e9),
    }
    for arch, (total, active) in expect.items():
        cfg = get_config(arch)
        t = cfg.param_count()
        a = cfg.param_count(active_only=True)
        assert abs(t - total) / total < 0.18, (arch, t, total)
        assert abs(a - active) / active < 0.25, (arch, a, active)


def test_moe_capacity_drops_tokens_in_training_mode():
    from repro.models.layers import KeyGen, init_moe, moe_block
    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    p, _ = init_moe(KeyGen(KEY), cfg)
    x = jax.random.normal(KEY, (2, 64, cfg.d_model),
                          jnp.dtype(cfg.dtype))
    out_cap, aux = moe_block(p, cfg, x, dropless=False)
    out_free, _ = moe_block(p, cfg, x, dropless=True)
    assert out_cap.shape == out_free.shape == x.shape
    assert float(aux["moe_load_balance"]) > 0.0


def test_cyclegan_smoke():
    from repro.configs.icf_cyclegan import SMOKE as CCFG
    from repro.models import icf_cyclegan as cg
    params, axes = cg.init_cyclegan(CCFG, KEY)
    x = jax.random.uniform(KEY, (8, CCFG.input_dim))
    y = jax.random.uniform(KEY, (8, CCFG.output_dim))
    loss, metrics = cg.generator_loss(params["gen"], params["disc"],
                                      CCFG, {"x": x, "y": y})
    dloss, dm = cg.discriminator_loss(params["disc"], params["gen"],
                                      CCFG, {"x": x, "y": y})
    assert jnp.isfinite(loss) and jnp.isfinite(dloss)
    pred = cg.predict(params["gen"], x)
    assert pred.shape == (8, CCFG.output_dim)
