"""Ahead-of-time compiles for a described TPU v5e at real widths.

Nothing here runs on a chip: the TPU compiler, installed beside JAX,
compiles for a v5e:2x2 topology that is described, not attached.  It
refuses what Mosaic refuses (unaligned block shapes, too much VMEM) and
programs that do not fit the chip's 16 GB, which interpret-mode tests
cannot show.  The topology is described inside a module-scoped fixture,
so only the worker that runs these tests loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import OptimizerConfig
from repro.configs.deepseek_v2_lite import FULL as DSV2
from repro.configs.icf_cyclegan import FULL as ICF_FULL
from repro.configs.qwen3_06b import FULL as QWEN3
from repro.kernels import latent_attention as la
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import lm
from repro.serve import sampling, session
from repro.train.steps import make_gan_steps

V5E_HBM_BYTES = 16 * 1024 ** 3
# qwen3-0.6b decode at serving shapes: 8 rows, 16-token pages, a
# 512-page pool (+ the null page) and 32 table columns (512 tokens), two
# layers stacked
B, BS, PAGES, W, LAYERS = 8, 16, 512, 32, 2


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
    pool = (LAYERS, PAGES + 1, BS, Hkv, D)
    args = [_spec(q, jnp.bfloat16, one_chip),
            _spec(pool, jnp.bfloat16, one_chip),
            _spec(pool, jnp.bfloat16, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((B, W), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip)]
    compiled = jax.jit(pa.paged_attention).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", [192, 128], ids=["chat", "overload"])
@pytest.mark.parametrize("k_tokens", [1, 4], ids=["decode", "verify"])
def test_paged_kernel_compiles_at_serving_cell_shapes(one_chip, k_tokens,
                                                      width):
    """The qwen3 cells' widest kernel calls: 64 rows over 192 table
    columns (chat, 3,072 tokens) or 128 (overload, 2,048), a 2,560-page
    pool (+ the null page), 28 layers stacked; Mosaic checks the real
    page block against VMEM and the tiling."""
    H, Hkv, D = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim
    rows, pages = 64, 2560
    q = (rows, H, D) if k_tokens == 1 else (rows, k_tokens, H, D)
    pool = (QWEN3.num_layers, pages + 1, BS, Hkv, D)
    compiled = jax.jit(pa.paged_attention).lower(
        _spec(q, jnp.bfloat16, one_chip),
        _spec(pool, jnp.bfloat16, one_chip),
        _spec(pool, jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((rows, width), jnp.int32, one_chip),
        _spec((rows,), jnp.int32, one_chip)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 ** 2


@pytest.mark.parametrize("k_tokens", [1, 4], ids=["decode", "verify"])
def test_latent_kernel_compiles_at_deepseek_v2_widths(one_chip, k_tokens):
    """16 query heads over one latent row of 576 values (512 of them the
    values), padded to 640 lanes, 16-token pages, 2,048 table columns
    (32k tokens), 64 rows, a two-layer stacked pool."""
    m, H, rows = DSV2.mla, DSV2.num_heads, 64
    q = (rows, H, m.pool_dim) if k_tokens == 1 \
        else (rows, k_tokens, H, m.pool_dim)

    def gather(q, pool, layer, tables, lengths):
        return la.latent_paged_attention(q, pool, layer, tables, lengths,
                                         scale=0.1,
                                         value_dim=m.kv_lora_rank)

    compiled = jax.jit(gather).lower(
        _spec(q, jnp.float32, one_chip),
        _spec((2, 4097, BS, m.pool_dim), jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((rows, 2048), jnp.int32, one_chip),
        _spec((rows,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 ** 2


@pytest.mark.parametrize("rows,k_tokens,dtype", [
    (64, 1, jnp.bfloat16), (64, 4, jnp.bfloat16), (1, 1, jnp.float32)],
    ids=["decode", "verify", "first-token"])
def test_device_sampler_compiles_at_qwen3_vocab(one_chip, rows, k_tokens,
                                                dtype):
    """The scheduler's sampler over the full 151,936-token vocabulary, at
    the serving cell's 64 slots (and one prefill row)."""
    V = QWEN3.vocab_size
    logits = (V,) if rows == 1 else (rows, k_tokens, V)
    compiled = sampling._draw.lower(
        _spec(logits, dtype, one_chip),
        _spec((rows,), jnp.float32, one_chip),
        _spec((rows, 2), jnp.uint32, one_chip),
        _spec((rows, k_tokens), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES // 16


def test_paged_attention_sharded_compiles_on_2x2_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    H, Hkv, D = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim
    pages = 2 * (PAGES // 2 + 1)       # two sub-pools, each + null page

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    pool = (LAYERS, pages, BS, Hkv, D)
    args = [_spec((B, H, D), jnp.bfloat16, on("data", "model", None)),
            _spec(pool, jnp.bfloat16, on(None, "data", None, "model", None)),
            _spec(pool, jnp.bfloat16, on(None, "data", None, "model", None)),
            _spec((), jnp.int32, on()),
            _spec((B, W), jnp.int32, on("data", None)),
            _spec((B,), jnp.int32, on("data"))]

    def gather(q, k, v, layer, tables, lengths):
        return ops.paged_attention_sharded(mesh, q, k, v, layer, tables,
                                           lengths, interpret=False)

    compiled = jax.jit(gather).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# an HLO instruction: ``%name = type[dims]{layout} opcode(``
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*\w+\[([\d,]*)\]\S*\s+"
                     r"([\w-]+)\(")


def _pool_copies(hlo: str, shapes):
    """Instructions of ``hlo`` that copy, slice or update-slice an
    array of one of ``shapes`` (dims of size 1 ignored), as themselves
    or as a fusion XLA named after them."""
    want = {tuple(d for d in s if d != 1) for s in shapes}
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if not m:
            continue
        name, dims, opcode = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d and d != "1")
        moves = any(w in f"{name} {opcode}" for w in
                    ("copy", "dynamic-slice", "dynamic-update-slice"))
        if moves and dims in want:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_qwen3_paged_programs_leave_the_pool_in_place(one_chip, monkeypatch,
                                                      program):
    """qwen3 FULL's decode step (64 rows, 2,560 pages, 192 table
    columns) and prefill chunk (512 tokens, 64 columns), with the Pallas
    kernel selected: the stacked K/V pool rides in the layer scan's
    carry, so no copy, slice or update-slice of the whole pool or of one
    layer's pool is compiled, and the temporaries stay far under a
    second pool."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    rows, pages = 64, 2560
    params = jax.eval_shape(lambda: lm.init_lm(QWEN3,
                                               jax.random.PRNGKey(0))[0])
    cache = jax.eval_shape(
        lambda: lm.init_cache(QWEN3, rows, pages=(pages, BS))[0])
    params, cache = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), (params, cache))

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    if program == "decode":
        lowered = session._step_tables_fn.lower(
            params, QWEN3, i32(rows, 1), cache, i32(rows), i32(rows, 192),
            None)
    else:
        lowered = session._chunk_fn.lower(
            params, QWEN3, i32(1, 512), cache, i32(1, 64), i32(), i32(),
            i32(1))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    k = cache["body"][0]["k"]
    assert k.shape == (QWEN3.num_layers, pages + 1, BS, QWEN3.num_kv_heads,
                       QWEN3.head_dim)
    assert _pool_copies(hlo, [k.shape, k.shape[1:]]) == []
    if program == "decode":
        assert "tpu_custom_call" in hlo
    pool_bytes = 2 * k.size * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.05 * pool_bytes


def test_pool_copies_finds_a_layer_slice():
    """The finder above sees the ops a scanned pool compiles to."""
    hlo = "\n".join([
        "  %dynamic-slice_bitcast_fusion.4 = bf16[2561,16,8,128]{3,2,1,0:"
        "T(8,128)(2,1)S(1)} fusion(%a, %b), kind=kLoop",
        "  %copy.81 = bf16[28,2561,16,8,128]{4,3,2,1,0} copy(%c)",
        "  %dynamic-slice.3 = bf16[1,2561,16,8,128]{4,3,2,1,0} "
        "dynamic-slice(%d, %e)",
        "  ROOT %scatter.19 = bf16[28,2561,16,8,128]{4,3,2,1,0} "
        "scatter(%p, %i, %u)",
        "  %copy.2 = bf16[64,8,128]{2,1,0} copy(%f)"])
    pool = (28, 2561, 16, 8, 128)
    assert len(_pool_copies(hlo, [pool, pool[1:]])) == 3


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
