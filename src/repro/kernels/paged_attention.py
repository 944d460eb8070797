"""Pallas TPU paged-attention decode/verify kernel (gather over
scattered KV pages).

The serving pool stores each layer's KV cache as one
``(num_pages + 1, block_size, n_kv_heads, head_dim)`` array; a request's
tokens live in whatever pages its block table names, in logical order
but physically scattered (the last page, index ``num_pages``, is the
null page that inactive batch rows point at).  This kernel computes
decode attention for a batch of requests directly against that layout,
for ``K >= 1`` query tokens per row — K = 1 is the classic decode step,
K > 1 is the speculative-decoding verify step where the K queries of a
row are consecutive positions of the same request:

  * grid = (batch, table_width) with the page dimension innermost, so
    the (kv_heads, K * group, head_dim) accumulator lives in VMEM
    scratch across a request's page sweep;
  * each grid step DMAs one whole page ``(block_size, kv_heads,
    head_dim)`` and loops over kv heads statically: Mosaic requires a
    block's last two dims to be (8, 128)-aligned or whole, so a block of
    one kv head out of the (kv_heads, head_dim) minor dims is refused,
    and so is a dynamic kv-head index into the page;
  * the block table and per-row sequence lengths ride in as
    **scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``): the
    K/V BlockSpec index_map reads ``tables[b, p]`` to DMA the right
    physical page HBM->VMEM — the gather never materializes a
    contiguous copy of the request's KV;
  * per-query causality: query t of row b attends over
    ``lengths[b] + t`` tokens (the intra-block staircase a K-token
    verify needs), expressed as a per-accumulator-row position bound;
  * pages wholly past every query's reach are skipped via ``pl.when``
    (their table entries are the null page), and the tail page is
    masked positionwise;
  * GQA is expressed by laying q out as (kv_heads, K * group) so q head
    ``h*g+j`` meets kv head ``h`` without duplication.

Numerics match ``repro.kernels.ref.paged_attention_ref``: online
softmax with running (m, l) in f32, output cast back to the query
dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, block_size: int,
            group: int, k_tokens: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    num_p = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages wholly past the LAST query's reach hold either the null
    # page or stale state — skip the compute, keep the accumulator
    @pl.when(p * block_size < length + k_tokens - 1)
    def _compute():
        n_kv, rows, _ = acc_ref.shape                     # Hkv, K * g
        pos = p * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        # accumulator row r is query token r // group: it reaches
        # length + r // group tokens (intra-block causal staircase)
        reach = length + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0) // group
        for h in range(n_kv):                             # static index
            q = q_ref[0, h].astype(jnp.float32) * scale   # (K*g, d)
            k = k_ref[0, :, h, :].astype(jnp.float32)     # (bs, d)
            v = v_ref[0, :, h, :].astype(jnp.float32)     # (bs, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(pos < reach, s, NEG_INF)        # tail-page mask
            m_prev = m_ref[h]                             # (K*g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            w = jnp.exp(s - m_new)
            w = jnp.where(s <= NEG_INF / 2, 0.0, w)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(w, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                w, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(p == num_p - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """Decode/verify attention over a paged KV pool.

    q: (B, H, D) single-token queries, or (B, K, H, D) for K
    consecutive query tokens per row (speculative verify);
    k_pages/v_pages: (num_pages [+1], block_size, Hkv, D) physical
    pools; tables: (B, W) int32 physical page ids (logical page j of
    row b at ``tables[b,j]``, null-page entries past the used length);
    lengths: (B,) int32 valid KV tokens for the FIRST query of each row
    (query t sees ``lengths[b] + t``).  Returns the same rank as ``q``.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, K, H, D = q.shape
    _, bs, Hkv, _ = k_pages.shape
    W = tables.shape[1]
    assert H % Hkv == 0
    g = H // Hkv
    scale = 1.0 / math.sqrt(D)
    # fold the K query tokens into the accumulator rows: row t*g + j is
    # (query token t, grouped head j) of kv head h
    qg = q.reshape(B, K, Hkv, g, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, K * g, D)

    kernel = functools.partial(_kernel, scale=scale, block_size=bs,
                               group=g, k_tokens=K)
    page = pl.BlockSpec((1, bs, Hkv, D),
                        lambda b, p, tbl, ln: (tbl[b, p], 0, 0, 0))
    rows = pl.BlockSpec((1, Hkv, K * g, D), lambda b, p, tbl, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[rows, page, page],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((Hkv, K * g, D), jnp.float32),
            pltpu.VMEM((Hkv, K * g, 1), jnp.float32),
            pltpu.VMEM((Hkv, K * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, K * g, D), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    out = out.reshape(B, Hkv, K, g, D).transpose(0, 2, 1, 3, 4) \
             .reshape(B, K, H, D)
    return out[:, 0] if squeeze else out
