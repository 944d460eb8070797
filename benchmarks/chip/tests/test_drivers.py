"""Each driver at a CPU size, called through the harness with its look
for a chip skipped: sound runs come out correct, and the timed path
broken underneath — once for each fault the cell can have — comes out
not correct, with the cell's own limits."""
import argparse

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import run as bench_run
from benchmarks.chip.tools import faults

SERVE = {
    "config": {"hidden_size": 256, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 64,
               "intermediate_size": 512, "vocab_size": 4096,
               "num_hidden_layers": 2, "kv_pool_pages": 256},
    "spec": {"slots": 4, "max_seq": 512,
             "check_tokens": 40,
             "traffic": {"rate_per_s": 4.0, "preroll_s": 1,
                         "system_prompt_tokens": 32,
                         "max_prompt_tokens": 200,
                         "message_tokens": {"median": 8, "sigma": 0.5,
                                            "lo": 4, "hi": 32},
                         "output_tokens": {"median": 6, "sigma": 0.5,
                                           "lo": 2, "hi": 12},
                         "greedy_share": 0.5}}}

# the sampled check reads a fixed shift per token against a noise that
# falls with the square root of the tokens read; the shift of a wrong
# temperature grows with the logits' spread over the temperature (0.02 x
# sqrt(hidden_size) for the benchmark's weights), so this size keeps the
# chip's hidden size and samples at 0.5: a sampler that ignores the
# temperature reads ~16 standard errors over ~650 tokens here (~7 at 0.7)
SERVE_SAMPLED = {
    "config": {**SERVE["config"], "hidden_size": 1024,
               "num_attention_heads": 8, "num_key_value_heads": 4,
               "head_dim": 128, "intermediate_size": 1024},
    "spec": {**SERVE["spec"], "slots": 8, "check_sampled_tokens": 4096,
             "traffic": {**SERVE["spec"]["traffic"], "rate_per_s": 6.0,
                         "greedy_share": 0.25, "temperature": 0.5,
                         "output_tokens": {"median": 48, "sigma": 0.5,
                                           "lo": 24, "hi": 96}}}}

LTFB = {
    "config": {"image_size": 8, "fwd_hidden": [32, 32],
               "inv_hidden": [32, 32], "disc_hidden": [32],
               "enc_hidden": [64], "dec_hidden": [64], "batch": 16},
    "spec": {
             "traffic": {"samples": 1024, "samples_per_file": 64,
                         "steps_per_round": 3}}}


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    """The readers need a peak table entry; the CPU has none, so the
    tests lend it the chip's (what they read is never a chip number)."""
    from benchmarks.chip import flops

    real = flops.peaks_for
    monkeypatch.setattr(flops, "peaks_for",
                        lambda kind: real("TPU v5 lite"))


def run_cell(workload, overrides, seed=2 ** 31 + 17, trace=0, seconds=2.0):
    args = argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=trace)
    out = bench_run.run_cell(args, require_tpu=False, overrides=overrides)
    assert out is not None
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_sound_run(trace):
    out = run_cell("qwen3-chat-shared", SERVE, trace=trace)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 8 and out["failed"] == 0
    m = out["metrics"]
    if trace:
        # the CPU's trace has no TPU device plane: the readers of the
        # device trace find nothing and leave their metrics out
        assert set(m) == {"queue_wait_p80_ms", "prefix_hit_share",
                          "gen_lag_p80_ms"}
        assert 0 < m["prefix_hit_share"]["value"] <= 100
        # the traced part is bounded by the harness's window span
        assert 0 < out["device"]["window_s"] <= 2.5
    else:
        assert set(m) == {"setup_s", "ttft_p80_ms", "itl_p95_ms",
                          "serve_tokens_per_s"}
        assert all(v["value"] > 0 for v in m.values())


def test_serve_altered_token_is_caught(monkeypatch):
    from repro.serve import scheduler

    inner = scheduler.Scheduler._sample

    def wrong(self, logits_row, req, ntok):
        tok = inner(self, logits_row, req, ntok)
        return (tok + 1) % len(logits_row) if req.temperature <= 0 else tok

    monkeypatch.setattr(scheduler.Scheduler, "_sample", wrong)
    out = run_cell("qwen3-chat-shared", SERVE)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.SAMPLER))
def test_serve_sampler_fault_is_caught(monkeypatch, fault):
    """Greedy tokens stay right; only the sampled check can see these."""
    faults.plant(fault, monkeypatch)
    out = run_cell("qwen3-chat-shared", SERVE_SAMPLED, seconds=3.0)
    assert out["checks"]["served_logit_gap"]["value"] <= \
        out["checks"]["served_logit_gap"]["limit"]
    assert not out["correct"], out["checks"]


def test_serve_step_that_keeps_its_state_is_caught(monkeypatch):
    from repro.serve import session

    inner = session.DecodeSession._call_step_tables

    def stale(self, *args):
        kept = jax.tree.map(jnp.copy, args[3])
        logits, _ = inner(self, *args)
        return logits, kept

    monkeypatch.setattr(session.DecodeSession, "_call_step_tables", stale)
    out = run_cell("qwen3-chat-shared", SERVE)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("trace", [0, 1])
def test_ltfb_sound_run(trace, ltfb_listed):
    out = run_cell("icf-ltfb-k4-4chip", LTFB, trace=trace)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["device"]["count"] == 4
    m = out["metrics"]
    if trace:
        assert set(m) == {"tournament_share.ltfb", "data_wait_share.ltfb",
                          "mfu.train"}
    else:
        assert set(m) == {"setup_s", "train_samples_per_s"}


def _patch_step(monkeypatch, wrap):
    from repro.train import steps

    inner = steps.make_gan_steps

    def make(*a, **k):
        init, step, metric = inner(*a, **k)
        return init, wrap(step), metric

    monkeypatch.setattr(steps, "make_gan_steps", make)


def test_ltfb_step_that_keeps_its_state_is_caught(monkeypatch, ltfb_listed):
    def wrap(step):
        def frozen(params, opt_state, batch, hparams):
            _, _, metrics = step(params, opt_state, batch, hparams)
            return params, opt_state, metrics
        return frozen

    _patch_step(monkeypatch, wrap)
    out = run_cell("icf-ltfb-k4-4chip", LTFB)
    assert not out["correct"], out["checks"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_ltfb_half_batch_is_caught(monkeypatch, ltfb_listed):
    def wrap(step):
        def half(params, opt_state, batch, hparams):
            n = len(batch["x"]) // 2
            return step(params, opt_state,
                        {k: v[:n] for k, v in batch.items()}, hparams)
        return half

    _patch_step(monkeypatch, wrap)
    out = run_cell("icf-ltfb-k4-4chip", LTFB)
    assert not out["correct"], out["checks"]


def test_ltfb_exchange_left_out_is_caught(monkeypatch, ltfb_listed):
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: x)
    out = run_cell("icf-ltfb-k4-4chip", LTFB)
    assert not out["correct"], out["checks"]
    assert out["checks"]["tournament_gap"]["value"] > \
        out["checks"]["tournament_gap"]["limit"]
