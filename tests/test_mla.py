"""DeepSeek-V2's latent attention (MLA) and its MoE against the plain
float32 reference of the chip benchmark (``benchmarks/chip/reference/
deepseek_v2.py``, which imports nothing of the program), on seeded
random weights at a CPU size.

The program runs in float32 here, so the two sides differ only in the
order of their sums (the program's absorbed decode form and chunked
prefill reassociate the same products): measured differences are about
1e-5 of logits of spread ~1, and every tolerance below is 1e-3.  A fault
that leaves out part of the mathematics (the shared rope key, the
latent's norm, the unnormalised gates) moves the logits by 1e-2 or
more, which the tests show by planting each one."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.drivers import serve_dsv2 as drv
from benchmarks.chip.reference import deepseek_v2 as ref
from repro.configs.registry import get_config
from repro.kernels import latent_attention, ops
from repro.kernels import ref as kref
from repro.models import layers
from repro.serve.kv_cache import PagedLayout
from repro.serve.scheduler import Request, Scheduler
from repro.serve.session import DecodeSession

# float32 on both sides: only the order of sums differs (~1e-5 seen)
TOL = 1e-3

# the published configuration with every width cut to a CPU size
SMALL = {
    "name": "deepseek-v2-small", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "moe_intermediate_size": 24, "vocab_size": 256, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "norm_topk_prob": False, "routed_scaling_factor": 1,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
}


@pytest.fixture(scope="module")
def model():
    weights = ref.make_weights(SMALL, jax.random.PRNGKey(7))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          drv.to_program(weights))
    return drv.model_config(SMALL), params, weights


@pytest.fixture
def fresh_programs():
    """Planted faults live inside jitted programs, cached by
    configuration: trace them anew, and leave no faulty one behind."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _served_logits(cfg, params, seq, prompt_len, chunk=8, block=4,
                   one_shot=False):
    """Prefill of ``seq[:prompt_len]`` (in chunks, or in one full-form
    call whose cache rows are scattered into the pool) and then one
    decode step per later token, through DecodeSession over a
    PagedLayout; returns the logits at positions prompt_len - 1 ..
    len(seq) - 2 and the layout (two idle rows beside the request's)."""
    lay = PagedLayout(cfg, num_slots=3, num_pages=48, block_size=block,
                      max_seq=96)
    sess = DecodeSession(cfg, params, lay)
    lay.admit("r", len(seq))
    lay.ensure("r", len(seq))
    W = lay.max_blocks_per_seq
    if one_shot:
        last = sess.prefill("r", seq[:prompt_len])
    for h in range(0, 0 if one_shot else prompt_len, chunk):
        n = min(chunk, prompt_len - h)
        last = sess.prefill_chunk("r", seq[h:h + n], h, prompt_len, chunk, W)
    out = [np.asarray(last)]
    slot = lay.slot_of("r")
    for i in range(prompt_len, len(seq) - 1):
        tok = np.zeros((3, 1), np.int32)
        idx = np.full((3,), -1, np.int32)
        tok[slot, 0], idx[slot] = seq[i], i
        out.append(np.asarray(sess.step(tok, idx, width=W)[slot, 0]))
    return np.stack(out), lay


def _reference_logits(weights, seq, prompt_len):
    rows = np.arange(prompt_len - 1, len(seq) - 1)
    return np.asarray(ref.logits(SMALL, weights, seq, rows, bucket=64))


def _gap(model, seq_len=40, prompt_len=27, one_shot=False):
    cfg, params, weights = model
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (seq_len,),
                                        0, cfg.vocab_size), np.int32)
    got, lay = _served_logits(cfg, params, seq, prompt_len,
                              one_shot=one_shot)
    want = _reference_logits(weights, seq, prompt_len)
    return float(np.abs(got - want).max()), lay, seq


@pytest.mark.parametrize("one_shot", [False, True],
                         ids=["chunked", "one_shot"])
def test_paged_prefill_and_decode_match_reference(model, one_shot):
    """Prefill (in chunks in the absorbed form, or in one call in the
    full form), then paged decode in the absorbed form over the stacked
    latent pool, against the reference's full forward."""
    prompt_len = 27
    err, lay, seq = _gap(model, prompt_len=prompt_len, one_shot=one_shot)
    assert err < TOL, err
    # the routing counters saw every real token once a layer (a one-shot
    # prefill counts none: only steps and chunks count), and no choice
    # was dropped
    counts = lay.moe_counts()
    cfg = model[0]
    fed = len(seq) - 1 - (prompt_len if one_shot else 0)
    assert counts["expert_tokens"][0].sum() == 0           # dense layer
    assert (counts["expert_tokens"][1:].sum(axis=1)
            == fed * cfg.moe.top_k).all()
    assert counts["dropped_tokens"].sum() == 0


def _drop_k_pe(monkeypatch):
    inner = layers._mla_project

    def project(params, cfg, x, positions):
        q_nope, q_pe, lat = inner(params, cfg, x, positions)
        return q_nope, q_pe, lat.at[..., cfg.mla.kv_lora_rank:].set(0)

    monkeypatch.setattr(layers, "_mla_project", project)


def _skip_kv_norm(monkeypatch):
    inner = layers.rmsnorm

    def norm(params, x, eps=1e-6):
        if params["scale"].shape == (SMALL["kv_lora_rank"],):
            return x * params["scale"].astype(x.dtype)
        return inner(params, x, eps)

    monkeypatch.setattr(layers, "rmsnorm", norm)


def _renormalise_gates(monkeypatch):
    inner = layers.moe_block

    def moe(params, cfg, x, dropless=False, mask=None):
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, norm_topk_prob=True))
        return inner(params, cfg, x, dropless=dropless, mask=mask)

    monkeypatch.setattr(layers, "moe_block", moe)


@pytest.mark.parametrize("plant", [_drop_k_pe, _skip_kv_norm,
                                   _renormalise_gates],
                         ids=["no_k_pe", "no_kv_a_layernorm",
                              "renormalised_gates"])
def test_parity_catches_a_planted_fault(model, monkeypatch, fresh_programs,
                                        plant):
    plant(monkeypatch)
    err, _, _ = _gap(model)
    assert err > 10 * TOL, err


def test_scheduler_serves_the_reference_greedy_tokens(model):
    """Through Scheduler (chunked prefill, prefix sharing, paged decode):
    every greedy token is the reference's best, or within the tolerance
    of it."""
    cfg, params, weights = model
    rng = np.random.default_rng(5)
    doc = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    sched = Scheduler(cfg, params, num_slots=4, max_len=96, block_size=4,
                      num_blocks=64, max_seq=96, prefill_chunk=8,
                      prefix_sharing=True, pin_prefix=True)
    prompts = [np.concatenate([doc, rng.integers(0, cfg.vocab_size, n)
                               .astype(np.int32)]) for n in (5, 9, 3)]
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=6, temperature=0.0))
    out = sched.run()
    assert sched.pool.prefix_shared_tokens > 0
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, out[i]])
        want = _reference_logits(weights, seq, len(p))[:len(out[i])]
        gap = want.max(-1) - want[np.arange(len(out[i])), out[i]]
        assert gap.max() < TOL, (i, gap)
    counts = sched.read_moe_counters()
    assert sched.stats.moe_dropped_tokens == 0
    assert sum(sched.stats.moe_routed_tokens) == \
        counts["expert_tokens"].sum() > 0
    # one pool row of (32 + 8) values, padded to 128 lanes, f32, 3 layers
    assert sched.stats.latent_cache_bytes_per_token == 3 * 128 * 4


@pytest.mark.parametrize("k_tokens", [1, 3], ids=["decode", "verify"])
def test_latent_kernel_matches_its_oracle(k_tokens):
    """The Pallas kernel in interpret mode against ``ref.
    latent_paged_attention_ref``: multi-block rows, a row shorter than a
    block, an idle row (length 0) and the pool's layer index."""
    key = jax.random.PRNGKey(1)
    B, H, D, R, pages, bs, W = 3, 4, 128, 64, 40, 16, 10
    pool = jax.random.normal(key, (2, pages + 1, bs, D), jnp.float32) \
        .astype(jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(0).integers(0, pages,
                                                           (B, W)),
                         jnp.int32)
    lengths = jnp.array([37, 0, 150], jnp.int32)
    shape = (B, H, D) if k_tokens == 1 else (B, k_tokens, H, D)
    q = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    got = latent_attention.latent_paged_attention(
        q, pool, 1, tables, lengths, scale=0.3, value_dim=R, block_pages=4,
        interpret=True)
    want = kref.latent_paged_attention_ref(q, pool, 1, tables, lengths, 0.3,
                                           R)
    # both in float32 over the same bf16 rows: summation order only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(got)[1].any()
    # the CPU dispatch is the oracle itself
    np.testing.assert_array_equal(
        np.asarray(ops.latent_paged_attention(q, pool, 1, tables, lengths,
                                              scale=0.3, value_dim=R)),
        np.asarray(want))


@pytest.mark.parametrize("norm_topk", [False, True],
                         ids=["published", "renormalised"])
def test_moe_block_gate_matches_reference(norm_topk):
    """``moe_block`` (dropless) against the reference's MoE layer: softmax
    over the router, top-k kept as they are (``norm_topk_prob`` false,
    DeepSeek) or renormalised, plus the shared experts."""
    cfg_json = {**SMALL, "norm_topk_prob": norm_topk}
    weights = ref.make_weights(cfg_json, jax.random.PRNGKey(11))
    cfg = drv.model_config(cfg_json)
    ffn = jax.tree.map(lambda a: a.astype(jnp.float32),
                       drv.to_program(weights)["body"][0]["ffn"])
    ffn = jax.tree.map(lambda a: a[0], ffn)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model),
                          jnp.float32)
    # the reference layer normalises its input and adds it back: give
    # both sides a normalised input and take the residual out
    h = x * jax.lax.rsqrt(jnp.mean(x ** 2, -1, keepdims=True) + 1e-6)
    got, aux = layers.moe_block(ffn, cfg, h[None], dropless=True,
                                mask=jnp.ones((1, 24), bool))
    ml = jax.tree.map(lambda a: a[0], weights["moe_layers"])
    p = {**{k: ml[k] for k in ref.EXPERTS + ref.SHARED + ("router",)},
         "ln2": jnp.ones((cfg.d_model,), jnp.bfloat16)}
    want = ref._moe_ffn(cfg_json, p, h, 24) - h
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=TOL, rtol=TOL)
    assert int(aux["expert_tokens"].sum()) == 24 * cfg.moe.top_k
    assert int(aux["dropped_tokens"]) == 0


def test_configs_hold_the_published_numbers():
    """The registry's FULL config and the benchmark's file carry
    DeepSeek-V2-Lite's published config.json numbers; the cut keeps
    every width and changes the depth alone."""
    import json

    from benchmarks.chip import harness

    full = get_config("deepseek-v2-lite")
    assert (full.num_layers, full.d_model, full.num_heads,
            full.vocab_size) == (27, 2048, 16, 102400)
    m = full.mla
    assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim, m.latent_dim, m.pool_dim) == (512, 128, 64, 128,
                                                        576, 640)
    e = full.moe
    assert (e.num_experts, e.top_k, e.num_shared_experts, e.d_expert,
            e.first_k_dense, e.dense_d_ff, e.norm_topk_prob) == \
        (64, 6, 2, 1408, 1, 10944, False)
    y = full.yarn
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    assert full.rope_theta == 10000 and full.norm_eps == 1e-6
    assert not full.tie_embeddings
    # 15.7B total; 2.4B active as published, which this count (2.66B)
    # passes by about the untied head
    assert abs(full.param_count() - 15.7e9) / 15.7e9 < 0.02
    assert abs(full.param_count(active_only=True) - 2.4e9) / 2.4e9 < 0.12
    assert get_config("deepseek-moe-16b").moe.norm_topk_prob is False
    # softmax scale: 192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2
    assert layers.mla_softmax_scale(full) == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)

    cfg = json.loads((harness.CHIP / "configs"
                      / "deepseek-v2-lite.json").read_text())
    published = {"hidden_size": 2048, "intermediate_size": 10944,
                 "kv_lora_rank": 512, "moe_intermediate_size": 1408,
                 "n_routed_experts": 64, "n_shared_experts": 2,
                 "num_attention_heads": 16, "num_experts_per_tok": 6,
                 "num_key_value_heads": 16, "q_lora_rank": None,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "vocab_size": 102400,
                 "first_k_dense_replace": 1, "norm_topk_prob": False,
                 "routed_scaling_factor": 1, "rope_theta": 10000,
                 "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
                 "max_position_embeddings": 163840}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]
            ) == (7, 27)
    small = drv.model_config(cfg)
    assert small.mla == full.mla and small.moe == full.moe \
        and small.yarn == full.yarn
    # the dense layer and 6 MoE layers: 4,009 M parameters
    assert abs(small.param_count() - 4.009e9) / 4.009e9 < 0.002


def test_latent_stack_needs_the_paged_pool():
    from repro.models.lm import init_cache

    with pytest.raises(ValueError, match="paged pool"):
        init_cache(get_config("deepseek-v2-lite", smoke=True), 2, 16)
