"""HLO analyzer validation: trip-count-corrected costs must match XLA's
cost_analysis on unrolled programs, and scans must scale with length."""
import jax
import jax.numpy as jnp
import pytest

from repro.parallel.hlo_analysis import analyze_hlo


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_unrolled_matches_xla_cost_analysis():
    def f(xs, w):
        c = w
        for i in range(5):
            c = c @ xs[i]
        return c

    co = _compile(f, jax.ShapeDtypeStruct((5, 64, 64), jnp.float32),
                  jax.ShapeDtypeStruct((64, 64), jnp.float32))
    mine = analyze_hlo(co.as_text()).flops
    xla = co.cost_analysis().get("flops", 0.0)
    assert abs(mine - xla) / xla < 0.05


def test_scan_flops_scale_with_trip_count():
    def mk(n):
        def f(xs, w):
            def body(c, x):
                return c @ x, ()
            out, _ = jax.lax.scan(body, w, xs)
            return out
        return _compile(f, jax.ShapeDtypeStruct((n, 64, 64), jnp.float32),
                        jax.ShapeDtypeStruct((64, 64), jnp.float32))

    f3 = analyze_hlo(mk(3).as_text()).flops
    f12 = analyze_hlo(mk(12).as_text()).flops
    assert f12 == pytest.approx(4 * f3, rel=0.05)


def test_trip_count_detected():
    def f(xs, w):
        def body(c, x):
            return c @ x, ()
        out, _ = jax.lax.scan(body, w, xs)
        return out

    co = _compile(f, jax.ShapeDtypeStruct((7, 32, 32), jnp.float32),
                  jax.ShapeDtypeStruct((32, 32), jnp.float32))
    cost = analyze_hlo(co.as_text())
    assert 7 in cost.trip_counts.values()


def test_scan_matches_unrolled_flops():
    def f_scan(xs, w):
        def body(c, x):
            return c @ x, ()
        return jax.lax.scan(body, w, xs)[0]

    def f_unroll(xs, w):
        c = w
        for i in range(6):
            c = c @ xs[i]
        return c

    s1 = jax.ShapeDtypeStruct((6, 48, 48), jnp.float32)
    s2 = jax.ShapeDtypeStruct((48, 48), jnp.float32)
    a = analyze_hlo(_compile(f_scan, s1, s2).as_text()).flops
    b = analyze_hlo(_compile(f_unroll, s1, s2).as_text()).flops
    assert a == pytest.approx(b, rel=0.05)


def test_dynamic_update_slice_counted_as_slice_traffic():
    def f(buf, upd):
        return jax.lax.dynamic_update_slice(buf, upd, (0, 0))

    big = jax.ShapeDtypeStruct((4096, 256), jnp.float32)
    small = jax.ShapeDtypeStruct((1, 256), jnp.float32)
    # donated buffer -> in-place DUS (the KV-cache decode pattern)
    co = jax.jit(f, donate_argnums=(0,)).lower(big, small).compile()
    cost = analyze_hlo(co.as_text())
    # traffic ~ 2x the update slice (1KB), NOT the 4MB buffer
    assert cost.bytes < 64 * 1024, cost.bytes


def test_collectives_counted_with_multiplier():
    import os, subprocess, sys
    script = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
from repro.parallel.hlo_analysis import analyze_hlo
mesh = Mesh(np.asarray(jax.devices()).reshape(4,), ("d",))
sh = NamedSharding(mesh, P(None, "d"))
def f(xs, w):
    def body(c, x):
        h = c @ x
        return jax.lax.with_sharding_constraint(h, sh), jnp.sum(h)
    return jax.lax.scan(body, w, xs)
co = jax.jit(f, in_shardings=(None, sh)).lower(
    jax.ShapeDtypeStruct((5,64,64), jnp.float32),
    jax.ShapeDtypeStruct((64,64), jnp.float32)).compile()
c = analyze_hlo(co.as_text())
assert c.coll_bytes > 0, c
assert any(v >= 5 for v in c.coll_count.values()), c.coll_count
print("OK")
"""
    env = dict(os.environ)
    env.update({"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PYTHONPATH": "src"})
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-2000:]
