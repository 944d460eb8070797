"""The yardstick's operation and byte counts."""
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import flops, harness

ICF = json.loads((harness.CHIP / "configs" / "icf-cyclegan.json").read_text())
QWEN = json.loads((harness.CHIP / "configs" / "qwen3-0.6b.json").read_text())

# XLA's cost analysis also counts the elementwise work the analytic
# count leaves out: Adam over ~101 M parameters (~12 operations each),
# the activations and the losses, about 1.5% of the step, and it reads
# one E(y) that both updates need once.  The analytic count must sit
# below XLA's by no more than that share and the slack of its rounding.
ICF_TOLERANCE = 0.03


def test_icf_count_agrees_with_xla_for_the_compiled_full_step():
    from repro.configs.base import OptimizerConfig
    from repro.train.steps import make_gan_steps

    from benchmarks.chip.drivers import ltfb

    ccfg = ltfb.program_config(ICF)
    init, step, _ = make_gan_steps(ccfg, OptimizerConfig(warmup_steps=1))
    p, o, h = jax.eval_shape(lambda: init(0))
    B = ICF["batch"]
    batch = {"x": jax.ShapeDtypeStruct((B, ccfg.input_dim), jnp.float32),
             "y": jax.ShapeDtypeStruct((B, ccfg.output_dim), jnp.float32)}
    xla = step.lower(p, o, batch, h).compile().cost_analysis()["flops"]
    ours = flops.icf_step_flops(ICF, B)
    assert abs(ours - xla) / xla < ICF_TOLERANCE, (ours, xla)
    assert ours <= xla
    assert flops.icf_param_count(ICF) == ccfg.param_count()


def test_decode_flops_from_shapes():
    s = flops.lm_shapes(QWEN)
    one = flops.decode_flops(QWEN, [1])
    # non-embedding matrices (~440 M) and the tied logits (~156 M)
    assert 1.15e9 < one < 1.25e9
    longer = flops.decode_flops(QWEN, [1001])
    assert longer - one == pytest.approx(
        s["layers"] * 4 * s["H"] * s["D"] * 1000)
    assert flops.decode_flops(QWEN, []) == 0


def test_paged_attention_cost_counts_live_pages():
    c = flops.paged_attention_cost(QWEN, [1, 16, 17, 0], block_size=16)
    page = 16 * 8 * 128 * 2 * 2           # K and V of one 16-token page
    qo = 2 * 16 * 128 * 2
    assert c["bytes"] == 1 * page + 1 * page + 2 * page + 3 * qo
    assert c["flops"] == 4 * 16 * 128 * (1 + 16 + 17)
    r = flops.roofline_seconds(c["flops"], c["bytes"],
                               flops.peaks_for("TPU v5 lite"))
    assert r["bound"] == "memory"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
