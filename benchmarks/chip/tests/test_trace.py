"""The trace reduction, on a trace recorded on a TPU v5e chip.

``testdata/probe.xplane.pb``: three host spans ``serve.step``, each
around a jitted bf16 matmul (512x1024 by 1024x1024, tanh) and a call of
the Pallas paged-attention kernel (8 rows, 16 pages of 16 tokens, 16
heads over 8 KV heads of 128), each followed by a 2 ms ``gen.submit``
span of host sleep."""
import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace as T

DATA = harness.CHIP / "testdata" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return T.load(str(DATA))


def test_planes(tr):
    assert tr.devices == [0]
    assert sum(n == "serve.step" for _, _, n in tr.host) == 3
    mods = T.module_seconds(tr, 0, *tr.window())
    assert set(mods) == {"jit__lambda", "jit_paged_attention"}


def test_kernel_and_busy_time(tr):
    lo, hi = tr.window()
    ops = T.op_seconds(tr, 0, lo, hi)
    assert set(ops) >= {"paged_attention", "convolution_tanh_fusion"}
    kernel = ops["paged_attention"]
    # three calls of ~77 us each
    assert 3 * 60e-6 < kernel < 3 * 100e-6
    busy = T.busy_seconds(tr, 0, lo, hi)
    assert kernel < busy <= sum(ops.values()) + 1e-12
    mods = T.module_seconds(tr, 0, lo, hi)
    assert abs(mods["jit_paged_attention"] - kernel) < 1e-6
    custom = T.ops_in_module(tr, 0, lo, hi, "jit_paged_attention",
                             lambda short, text: "tpu_custom_call" in text)
    assert custom == pytest.approx(kernel)
    assert T.ops_in_module(tr, 0, lo, hi, "jit__lambda",
                           lambda short, text: "tpu_custom_call" in text) == 0


def test_summary_attributes_idle_gaps(tr):
    s = T.summarize(tr, [0], prefer=harness.SPANS)
    assert s["device_ops"][0][0] == "paged_attention"
    assert 0 < s["busy_s"] < s["window_s"]
    labels = dict(s["idle_gaps"])
    assert labels["gen.submit"] > 3 * 1.5e-3
    assert "serve.step" in labels or any(k.startswith("serve.step>")
                                         for k in labels)
    idle = sum(v for _, v in s["idle_gaps"])
    assert abs(idle + s["busy_s"] - s["window_s"]) < 1e-6
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10


def test_interval_arithmetic():
    ivs = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c")]
    assert T.merged(ivs, 0, 100) == [(0, 20), (30, 40)]
    assert T.merged(ivs, 8, 35) == [(8, 20), (30, 35)]
    tr = T.Trace(ops={0: ivs}, host=[(0, 100, "serve.step"),
                                     (22, 28, "inner")])
    assert T.gaps(tr, 0, 0, 50) == [(20, 30), (40, 50)]
    assert T.busy_seconds(tr, 0, 0, 50) == 30 / 1e9
    assert T.host_label(tr, 25, prefer=("serve.step",)) == \
        "serve.step>inner"
    assert T.host_label(tr, 45, prefer=("serve.step",)) == "serve.step"
    assert T.host_label(tr, 150) == "idle"


def test_host_spans_with_python_tracing_off(tmp_path):
    """The harness traces with the Python tracer off, so its annotations
    sit on its thread's line; the reduction finds them there and bounds
    the window by ``bench.window``."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("before"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("serve.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    assert sum(n == "serve.step" for _, _, n in tr.host) == 3
    lo, hi = tr.window()
    (before,) = [(s, e) for s, e, n in tr.host if n == "before"]
    assert before[1] <= lo < hi
    s, e, _ = [iv for iv in tr.host if iv[2] == "serve.step"][1]
    assert lo <= s < e <= hi
    assert T.host_label(tr, (s + e) / 2, prefer=harness.SPANS) \
        .startswith("serve.step")


def test_op_names():
    assert T.op_name("%paged_attention.1 = bf16[8,8,2,128] custom-call(")\
        == "paged_attention"
    assert T.op_name("%copy-start = (bf16[1024]) copy-start(") == \
        "copy-start"
    assert T.op_name("%fusion.12 = f32[] fusion(") == "fusion"
    assert T.module_name("jit__step_tables_fn(123)") == \
        "jit__step_tables_fn"
