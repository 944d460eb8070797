"""Pallas TPU decode kernel for multi-head latent attention over a
paged latent pool.

Every layer of an MLA model caches one row per token, ``[c_kv; k_pe]``
(512 + 64 values for DeepSeek-V2, zero-padded to 640 lanes: a DMA of a
whole page needs a 128-aligned minor dim, and the chip's tiled layout
pads 576 to 640 in HBM anyway), in a page pool that is stacked over
layers from the start: ``(layers, num_pages + 1, block_size, width)``,
the last page of each layer the null page.
In the absorbed decode form all query heads meet the same rows, as one
shared KV "head": scores over the whole row (the queries are zero over
its padding), values over its first ``value_dim`` columns (``c_kv``),
from the same page.

  * grid = (batch,): one row per step; its pages are read ``block_pages``
    at a time by manual DMAs from HBM (``memory_space=ANY``) into a
    double buffer, the next block's copies started before this block's
    compute, so the page reads overlap the arithmetic;
  * the pool is never sliced per layer: the layer index rides in as a
    scalar-prefetch operand beside the row lengths, and each copy reads
    ``pool[layer, tables[b, j]]`` — one whole page, ``(block_size,
    latent_dim)``;
  * the row's block-table row comes in as an SMEM block, so the table
    held on chip is one row, whatever the batch;
  * rows of length 0 (idle slots) read nothing and return zeros; blocks
    past a row's reach are never fetched, and the last block is masked
    positionwise;
  * K >= 1 query tokens per row are folded into the accumulator rows
    (row ``t * heads + h``); token t of a row sees ``length + t`` tokens.

Numerics match ``repro.kernels.ref.latent_paged_attention_ref``: scores
and the online softmax in float32, output in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, layer_ref, table_ref, q_ref, pool_hbm, o_ref,
            buf, sem, *, scale: float, block_size: int, block_pages: int,
            heads: int, k_tokens: int, value_dim: int):
    b = pl.program_id(0)
    length = lengths_ref[b]
    layer = layer_ref[0]
    tk = block_pages * block_size
    rows, width = q_ref.shape[1], q_ref.shape[2]
    n_blocks = jnp.where(length > 0, (length + k_tokens - 1 + tk - 1) // tk,
                         0)

    def copies(j, slot):
        return [pltpu.make_async_copy(
            pool_hbm.at[layer, table_ref[0, 0, j * block_pages + i]],
            buf.at[slot, i], sem.at[slot]) for i in range(block_pages)]

    @pl.when(n_blocks == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _attend():
        for cp in copies(0, 0):
            cp.start()
        q = q_ref[0].astype(jnp.float32) * scale             # (rows, width)
        # accumulator row r is query token r // heads
        reach = length + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tk), 0) // heads

        def body(j, carry):
            m_run, l_run, acc = carry
            slot = j % 2

            @pl.when(j + 1 < n_blocks)
            def _prefetch():
                for cp in copies(j + 1, 1 - slot):
                    cp.start()

            for cp in copies(j, slot):
                cp.wait()
            kv = buf[slot].astype(jnp.float32).reshape(tk, width)
            s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 1)
            s = jnp.where(pos < reach, s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=1, keepdims=True))
            w = jnp.exp(s - m_new)
            w = jnp.where(s <= NEG_INF / 2, 0.0, w)
            corr = jnp.exp(m_run - m_new)
            l_run = l_run * corr + jnp.sum(w, axis=1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                w, kv[:, :value_dim], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_run, acc

        init = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, value_dim), jnp.float32))
        _, l_run, acc = jax.lax.fori_loop(0, n_blocks, body, init)
        o_ref[0] = (acc / jnp.maximum(l_run, 1e-30)).astype(o_ref.dtype)


def latent_paged_attention(q: jax.Array, pool: jax.Array, layer,
                           tables: jax.Array, lengths: jax.Array,
                           scale: float, value_dim: int,
                           block_pages: int = 64,
                           interpret: bool = False) -> jax.Array:
    """Absorbed latent attention of each row's queries over its pages.

    q: (B, H, D) or (B, K, H, D) queries in the rows' space (D the
    pool's row width, a multiple of 128); pool: (layers, P+1, bs, D)
    stacked latent pool; layer: int32 scalar; tables: (B, W) int32 page
    ids (null-page entries past a row's pages); lengths: (B,) int32
    tokens the FIRST query of each row sees (0: an idle row).  Returns float32 of q's rank with last
    dim ``value_dim``: the softmax-weighted first ``value_dim`` columns
    of the rows.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, K, H, D = q.shape
    _, _, bs, _ = pool.shape
    W = tables.shape[1]
    block_pages = min(block_pages, W)
    nb = -(-W // block_pages)
    null_page = pool.shape[1] - 1
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, nb * block_pages - W)),
                     constant_values=null_page)
    qf = q.astype(jnp.float32).reshape(B, K * H, D)
    kernel = functools.partial(
        _kernel, scale=float(scale), block_size=bs, block_pages=block_pages,
        heads=H, k_tokens=K, value_dim=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, nb * block_pages),
                         lambda b, ln, ly: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, K * H, D), lambda b, ln, ly: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K * H, value_dim),
                               lambda b, ln, ly: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, bs, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * H, value_dim), jnp.float32),
        interpret=interpret,
    )(lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      tables.reshape(B, 1, nb * block_pages), qf, pool)
    out = out.reshape(B, K, H, value_dim)
    return out[:, 0] if squeeze else out
