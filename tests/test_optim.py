"""Optimizer + compression tests, incl. hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.configs.base import OptimizerConfig
from repro.optim import compression as comp
from repro.optim import optimizers as opt_lib

KEY = jax.random.PRNGKey(3)


def test_adam_converges_on_quadratic():
    cfg = OptimizerConfig(name="adam", lr=0.1, warmup_steps=1)
    opt = opt_lib.make_optimizer(cfg)
    params = {"w": jnp.array([5.0, -3.0]),
              "nest": ({"b": jnp.array([2.0])},)}   # tuple internal node
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2) + jnp.sum(p["nest"][0]["b"] ** 2)
    for _ in range(200):
        grads = jax.grad(loss)(params)
        params, state = opt.update(grads, state, params)
    assert float(loss(params)) < 1e-3


def test_adafactor_converges_on_matrix_quadratic():
    cfg = OptimizerConfig(name="adafactor", lr=0.05)
    opt = opt_lib.make_optimizer(cfg)
    params = {"W": jnp.ones((4, 8)) * 3.0, "b": jnp.ones((8,))}
    state = opt.init(params)
    # factored second moment shapes
    assert state["vr"]["W"].shape == (4,)
    assert state["vc"]["W"].shape == (8,)
    loss = lambda p: jnp.sum(p["W"] ** 2) + jnp.sum(p["b"] ** 2)
    l0 = float(loss(params))
    for _ in range(400):
        params, state = opt.update(jax.grad(loss)(params), state, params)
    # update clipping (rms<=1) bounds steady-state error at ~lr per coord
    assert float(loss(params)) < 0.01 * l0


def test_clip_by_global_norm():
    grads = {"a": jnp.full((10,), 10.0)}
    clipped, norm = opt_lib.clip_by_global_norm(grads, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(1000.0), rel=1e-5)
    assert float(opt_lib.global_norm(clipped)) == pytest.approx(1.0,
                                                                rel=1e-4)


def test_lr_schedule_warmup_and_cosine():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, schedule="cosine",
                          total_steps=110)
    assert float(opt_lib.lr_schedule(cfg, jnp.int32(0))) == 0.0
    assert float(opt_lib.lr_schedule(cfg, jnp.int32(10))) \
        == pytest.approx(1.0)
    assert float(opt_lib.lr_schedule(cfg, jnp.int32(110))) \
        == pytest.approx(0.0, abs=1e-6)


@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=1,
                max_size=64))
@settings(max_examples=50, deadline=None)
def test_int8_quantization_error_bound(vals):
    x = jnp.asarray(vals, jnp.float32)
    q, scale = comp.quantize_int8(x)
    err = jnp.max(jnp.abs(comp.dequantize_int8(q, scale) - x))
    # max error is half a quantization step
    assert float(err) <= float(scale) * 0.5 + 1e-6


def test_compressed_psum_pod_matches_mean():
    """2-pod compressed all-reduce == true mean within quantization err,
    and error feedback drives the *accumulated* bias to zero."""
    import subprocess, sys, os
    script = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.optim import compression as comp

mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2,), ("pod",))
g = jnp.stack([jnp.linspace(-1, 1, 64), jnp.linspace(2, -2, 64)])  # (2,64)
e = jnp.zeros((2, 64))

def body(gb, eb):
    # per-pod blocks are (1, 64)
    mean, err = comp.compressed_psum_pod({"g": gb[0]}, {"g": eb[0]},
                                         "pod", 2)
    return mean["g"][None], err["g"][None]

f = jax.jit(jax.shard_map(body, mesh=mesh,
            in_specs=(P("pod"), P("pod")),
            out_specs=(P("pod"), P("pod")), check_vma=False))
mean_ref = np.asarray(jnp.mean(g, axis=0))
out, err = f(g, e)
out = np.asarray(out)
# every pod holds the (quantized) mean
assert np.allclose(out[0], mean_ref, atol=0.03), np.abs(out[0]-mean_ref).max()
assert np.allclose(out[1], mean_ref, atol=0.03), np.abs(out[1]-mean_ref).max()
print("OK")
"""
    env = dict(os.environ)
    env.update({"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "PYTHONPATH": "src"})
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


@given(st.integers(0, 6))
@settings(max_examples=7, deadline=None)
def test_error_feedback_preserves_signal_over_steps(seed):
    """Error-feedback quantization: the accumulated transmitted signal
    converges to the accumulated true signal (no systematic bias)."""
    rng = np.random.default_rng(seed)
    true_sum = np.zeros(32)
    sent_sum = np.zeros(32)
    e = jnp.zeros(32)
    for _ in range(50):
        g = jnp.asarray(rng.normal(0, 1, 32), jnp.float32)
        acc = g + e
        q, s = comp.quantize_int8(acc)
        sent = comp.dequantize_int8(q, s)
        e = acc - sent
        true_sum += np.asarray(g)
        sent_sum += np.asarray(sent)
    # residual bias is bounded by one quantization step, NOT O(steps)
    assert np.max(np.abs(true_sum - sent_sum)) < 0.2
