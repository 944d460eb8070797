"""The DeepSeek-V2 serving driver at a CPU size, called through the
harness with its look for a chip skipped: a sound run comes out correct,
and the float8 control and the program broken underneath (the shared
rope key dropped, the latent's norm skipped, the top-k gates
renormalised) come out not correct, with the cell's own limits."""
import argparse
import time

import pytest

from benchmarks.chip import harness
from benchmarks.chip import run as bench_run

CELL = "dsv2lite-docqa-32k"

DSV2 = {
    "config": {"hidden_size": 256, "num_attention_heads": 4,
               "num_key_value_heads": 4, "kv_lora_rank": 64,
               "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
               "v_head_dim": 32, "intermediate_size": 512,
               "n_routed_experts": 8, "num_experts_per_tok": 3,
               "n_shared_experts": 2, "moe_intermediate_size": 96,
               "vocab_size": 2048, "num_hidden_layers": 3,
               "kv_pool_pages": 512},
    "spec": {"slots": 4, "max_seq": 512, "prefill_chunk": 64,
             "check_tokens": 40, "check_sampled_tokens": 60,
             "traffic": {"rate_per_s": 3.0, "preroll_s": 1, "lanes": 4,
                         "system_prompts": 2, "system_prompt_tokens": 160,
                         "max_turns": 3, "max_prompt_tokens": 320,
                         "message_tokens": {"median": 12, "sigma": 0.5,
                                            "lo": 4, "hi": 40},
                         "output_tokens": {"median": 8, "sigma": 0.5,
                                           "lo": 3, "hi": 16},
                         "greedy_share": 0.5}}}


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    """The readers need a peak table entry; the CPU has none, so the
    tests lend it the chip's (what they read is never a chip number)."""
    from benchmarks.chip import flops

    real = flops.peaks_for
    monkeypatch.setattr(flops, "peaks_for",
                        lambda kind: real("TPU v5 lite"))


def run_cell(overrides=DSV2, seed=2 ** 31 + 29, trace=0, seconds=2.0):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace)
    out = bench_run.run_cell(args, require_tpu=False, overrides=overrides)
    assert out is not None
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_dsv2_sound_run(trace):
    out = run_cell(trace=trace)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["moe_dropped_tokens"]["value"] == 0
    m = out["metrics"]
    if trace:
        # no TPU plane in the CPU's trace: only the host and counter
        # readers find something
        assert set(m) == {"queue_wait_p80_ms", "prefix_hit_share",
                          "gen_lag_p80_ms", "expert_load_imbalance"}
        assert m["prefix_hit_share"]["value"] > 50
        assert m["expert_load_imbalance"]["value"] >= 1.0
    else:
        assert set(m) == {"setup_s", "ttft_p80_ms", "itl_p95_ms",
                          "serve_tokens_per_s"}
        assert all(v["value"] > 0 for v in m.values())


def test_dsv2_control_is_not_correct():
    bench = harness.load_manifest()
    cell, spec, config = harness.cell_files(bench, CELL)
    config.update(DSV2["config"])
    for k, v in DSV2["spec"].items():
        spec[k] = {**spec[k], **v} if isinstance(v, dict) else v
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 5, seconds=2.0,
                              trace=0)
    run = harness.Run(args, cell, spec, config, time.perf_counter())
    drv = harness.load_file(harness.CHIP / "drivers" / "serve_dsv2.py",
                            "drv_dsv2_control")
    got = {c.name: c for c in drv.measure(run).check(control=True)}
    assert got["served_logit_gap"].ok
    assert not got["control_served_logit_gap"].ok, got


def _drop_k_pe(monkeypatch):
    from repro.models import layers

    inner = layers._mla_project

    def project(params, cfg, x, positions):
        q_nope, q_pe, lat = inner(params, cfg, x, positions)
        rank = cfg.mla.kv_lora_rank
        return q_nope, q_pe, lat.at[..., rank:].set(0)

    monkeypatch.setattr(layers, "_mla_project", project)


def _skip_kv_norm(monkeypatch):
    from repro.models import layers

    inner = layers.rmsnorm

    def norm(params, x, eps=1e-6):
        if x.shape[-1] == 64 and params["scale"].shape == (64,):
            return x * params["scale"].astype(x.dtype)
        return inner(params, x, eps)

    monkeypatch.setattr(layers, "rmsnorm", norm)


def _renormalise_gates(monkeypatch):
    import dataclasses

    from repro.models import layers

    inner = layers.moe_block

    def moe(params, cfg, x, dropless=False, mask=None):
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, norm_topk_prob=True))
        return inner(params, cfg, x, dropless=dropless, mask=mask)

    monkeypatch.setattr(layers, "moe_block", moe)


@pytest.fixture
def fresh_programs():
    """Planted faults live inside jitted programs, which are cached by
    configuration: trace them anew, and leave no faulty one behind."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("plant", [_drop_k_pe, _skip_kv_norm,
                                   _renormalise_gates],
                         ids=["no_k_pe", "no_kv_a_layernorm",
                              "renormalised_gates"])
def test_dsv2_program_fault_is_caught(monkeypatch, fresh_programs, plant):
    plant(monkeypatch)
    out = run_cell()
    assert not out["correct"], out["checks"]
    assert not out["checks"]["served_logit_gap"]["value"] <= \
        out["checks"]["served_logit_gap"]["limit"]
