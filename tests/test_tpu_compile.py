"""Ahead-of-time compiles for a described TPU v5e at real widths.

Nothing here runs on a chip: the TPU compiler, installed beside JAX,
compiles for a v5e:2x2 topology that is described, not attached.  It
refuses what Mosaic refuses (unaligned block shapes, too much VMEM) and
programs that do not fit the chip's 16 GB, which interpret-mode tests
cannot show.  The topology is described inside a module-scoped fixture,
so only the worker that runs these tests loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import OptimizerConfig
from repro.configs.icf_cyclegan import FULL as ICF_FULL
from repro.configs.qwen3_06b import FULL as QWEN3
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.train.steps import make_gan_steps

V5E_HBM_BYTES = 16 * 1024 ** 3
# qwen3-0.6b decode at serving shapes: 8 rows, 16-token pages, a
# 512-page pool (+ the null page) and 32 table columns (512 tokens)
B, BS, PAGES, W = 8, 16, 512, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k_tokens", [1, 4], ids=["decode", "verify"])
def test_paged_kernel_compiles_at_qwen3_shapes(one_chip, k_tokens):
    H, Hkv, D = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim
    q = (B, H, D) if k_tokens == 1 else (B, k_tokens, H, D)
    pool = (PAGES + 1, BS, Hkv, D)
    args = [_spec(q, jnp.bfloat16, one_chip),
            _spec(pool, jnp.bfloat16, one_chip),
            _spec(pool, jnp.bfloat16, one_chip),
            _spec((B, W), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip)]
    compiled = jax.jit(pa.paged_attention).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_sharded_compiles_on_2x2_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    H, Hkv, D = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim
    pages = 2 * (PAGES // 2 + 1)       # two sub-pools, each + null page

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    args = [_spec((B, H, D), jnp.bfloat16, on("data", "model", None)),
            _spec((pages, BS, Hkv, D), jnp.bfloat16,
                  on("data", None, "model", None)),
            _spec((pages, BS, Hkv, D), jnp.bfloat16,
                  on("data", None, "model", None)),
            _spec((B, W), jnp.int32, on("data", None)),
            _spec((B,), jnp.int32, on("data"))]

    def gather(q, k, v, tables, lengths):
        return ops.paged_attention_sharded(mesh, q, k, v, tables, lengths,
                                           interpret=False)

    compiled = jax.jit(gather).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_icf_cyclegan_train_step_fits_one_chip(one_chip):
    init, train_step, _ = make_gan_steps(
        ICF_FULL, OptimizerConfig(name="adam", lr=1e-3))
    params, opt_state, _ = jax.eval_shape(lambda: init(0))
    params, opt_state = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), (params, opt_state))
    batch = {"x": _spec((128, ICF_FULL.input_dim), jnp.float32, one_chip),
             "y": _spec((128, ICF_FULL.output_dim), jnp.float32, one_chip)}
    hparams = {"lr": _spec((), jnp.float32, one_chip)}
    compiled = train_step.lower(params, opt_state, batch,
                                hparams).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # params + Adam moments alone are ~1.2 GB at these widths
    assert mem.argument_size_in_bytes > ICF_FULL.param_count() * 4 * 3
    assert total < V5E_HBM_BYTES
