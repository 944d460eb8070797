"""Pallas TPU paged-attention decode/verify kernel over scattered KV
pages.

The serving pool stores a stack's KV cache as one ``(layers, num_pages +
1, block_size, n_kv_heads, head_dim)`` array for K and one for V; a
request's tokens live in whatever pages its block table names, in
logical order but physically scattered (the last page, index
``num_pages``, is the null page that idle batch rows write to).  The
kernel reads one layer of the stacked pool in place, so the layer scan
that carries the pool never slices a layer's pool out.  It computes
decode attention for a batch of requests directly against that layout,
for ``K >= 1`` query tokens per row — K = 1 is the classic decode step,
K > 1 is the speculative-decoding verify step where the K queries of a
row are consecutive positions of the same request:

  * grid = (batch,): one row per step.  K and V stay in HBM
    (``memory_space=ANY``); a ``fori_loop`` over the row's own blocks
    copies ``block_pages`` whole pages ``(block_size, kv_heads,
    head_dim)`` of K and of V at a time into a double buffer in VMEM,
    the next block's copies started before this block's compute.  A
    row's last block starts the next row's first, so a row's first
    pages arrive while the row before it computes;
  * the loop runs ``ceil((length + K - 1) / (block_pages * block_size))``
    times, from the row's own length, not the table's width, and a
    page's copy is issued only if the last query reaches into it: the
    tail of the last block is not fetched.  Rows of length 0 (idle
    slots) read nothing and return zeros;
  * the pool is never sliced per layer: the layer index, the row
    lengths and the block tables ride in as scalar-prefetch operands,
    and each copy reads ``pool[layer, tables[b, j]]``;
  * all kv heads in one product: a block's pages are a ``(tokens *
    kv_heads, head_dim)`` matrix as they lie in VMEM, and every query
    row — (kv head, query token, grouped head) — meets every column of
    it on the MXU; a column of another kv head is masked out, like a
    position past the row's reach, so no head is sliced or transposed
    out of the buffer;
  * per-query causality: query t of row b attends over
    ``lengths[b] + t`` tokens (the intra-block staircase a K-token
    verify needs), expressed as a per-accumulator-row position bound.

Bytes read against bytes credited: a row reads its live pages whole,
``ceil((length + K - 1) / block_size)`` pages of K and of V (the DMA
unit is a page), its query block and its output block — what
``benchmarks/chip/flops.paged_attention_cost`` credits for K = 1.
Nothing else of the pool is read, whatever the table's width.  The
MXU does ``kv_heads`` times the credited operations (the masked
cross-head columns), which stays far below the memory bound.

Numerics match ``repro.kernels.ref.paged_attention_ref``: scores in f32
(the query meets the keys in their own dtype with f32 accumulation,
which is exact for bf16 products, and the 1/sqrt(D) scale is applied to
the f32 scores), online softmax with running (m, l) in f32, the f32
softmax weights meet V in f32, output cast back to the query dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the widest score block, in (token, kv head) columns: block_pages is
# clipped so that tokens * kv_heads stays within it
MAX_COLUMNS = 2048


def _kernel(lengths_ref, layer_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, acc_ref, m_ref, l_ref, state, *,
            scale: float, block_size: int, block_pages: int,
            table_width: int, group: int, k_tokens: int):
    b = pl.program_id(0)
    n_batch = pl.num_programs(0)
    layer = layer_ref[0]
    tk = block_pages * block_size
    n_kv = k_buf.shape[2]
    n_rows = acc_ref.shape[0]                             # Hkv * K * g
    rows = n_rows // n_kv                                 # K * g

    def n_pages_of(r):
        """Pages the last query of row r reaches (none if it is idle)."""
        ln = lengths_ref[r]
        return jnp.where(
            ln > 0,
            jnp.minimum((ln + k_tokens - 1 + block_size - 1) // block_size,
                        table_width), 0)

    def pages(r, n_pages, j, slot, start: bool):
        """Start (or wait for) the copies of row r's block j."""
        for i in range(block_pages):
            @pl.when(j * block_pages + i < n_pages)
            def _page():
                page = table_ref[r * table_width + j * block_pages + i]
                dst = pl.ds(i * block_size, block_size)
                for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    cp = pltpu.make_async_copy(hbm.at[layer, page],
                                               buf.at[slot, dst],
                                               sem.at[slot])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

    length = lengths_ref[b]
    n_pages = n_pages_of(b)
    n_blocks = (n_pages + block_pages - 1) // block_pages
    nxt = jnp.minimum(b + 1, n_batch - 1)
    n_pages_next = jnp.where(b + 1 < n_batch, n_pages_of(nxt), 0)

    # state[0]: the buffer slot of this row's first block; state[1]: 1
    # if the row before already started that block's copies.  A buffer
    # position the last block does not fetch keeps what an earlier copy
    # left there, and its weight is 0; zero V once, so that 0 *
    # (never-written VMEM) cannot make a NaN
    @pl.when(b == 0)
    def _first():
        v_buf[...] = jnp.zeros_like(v_buf)
        state[0] = 0
        state[1] = 0

    base = state[0]

    @pl.when(state[1] == 0)
    def _start_own():
        pages(b, n_pages, 0, base, start=True)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # accumulator row i is kv head i // rows and query token
    # (i % rows) // group, which reaches length + that token (the
    # intra-block causal staircase); column c is token c // n_kv of
    # the block and kv head c % n_kv
    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, tk * n_kv), 0)
    head = row // rows
    reach = length + (row % rows) // group
    col = jax.lax.broadcasted_iota(jnp.int32, (n_rows, tk * n_kv), 1)
    same_head = col % n_kv == head
    col_token = col // n_kv

    def body(j, carry):
        slot = (base + j) % 2

        @pl.when(j + 1 < n_blocks)
        def _prefetch():
            pages(b, n_pages, j + 1, 1 - slot, start=True)

        @pl.when(j + 1 == n_blocks)
        def _prefetch_next_row():
            pages(nxt, n_pages_next, 0, 1 - slot, start=True)

        pages(b, n_pages, j, slot, start=False)
        k = k_buf[slot].reshape(tk * n_kv, -1)            # (tk*Hkv, d)
        q = q_ref[0]                                      # (Hkv*K*g, d)
        if q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        live = same_head & (col_token < reach - j * tk)
        s = jnp.where(live, s * scale, NEG_INF)
        m_prev = m_ref[...]                               # (Hkv*K*g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        w = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(w, axis=1, keepdims=True)
        v = v_buf[slot].reshape(tk * n_kv, -1).astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            w, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    # an idle row starts nothing for the next row, which starts its own
    state[0] = (base + n_blocks) % 2
    state[1] = jnp.where((n_blocks > 0) & (n_pages_next > 0), 1, 0)
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    layer, tables: jax.Array, lengths: jax.Array,
                    block_pages: int = 16,
                    interpret: bool = False) -> jax.Array:
    """Decode/verify attention over layer ``layer`` of a stacked paged
    KV pool.

    q: (B, H, D) single-token queries, or (B, K, H, D) for K
    consecutive query tokens per row (speculative verify);
    k_pages/v_pages: (layers, num_pages [+1], block_size, Hkv, D)
    stacked physical pools; layer: int32 scalar, the layer read;
    tables: (B, W) int32 physical page ids (logical page j of row b at
    ``tables[b,j]``, null-page entries past the used length);
    lengths: (B,) int32 valid KV tokens for the FIRST query of each row
    (query t sees ``lengths[b] + t``; 0 marks an idle row, which reads
    nothing and returns zeros).  ``block_pages`` pages are copied per
    step of a row's loop (clipped to W, and so that a block holds at
    most ``MAX_COLUMNS`` token-head pairs).  Returns the same rank as
    ``q``.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, K, H, D = q.shape
    _, _, bs, Hkv, _ = k_pages.shape
    W = tables.shape[1]
    assert H % Hkv == 0
    g = H // Hkv
    block_pages = max(1, min(block_pages, W, MAX_COLUMNS // (bs * Hkv)))
    # fold the K query tokens into the accumulator rows: row
    # (h * K + t) * g + j is (kv head h, query token t, grouped head j)
    qg = q.reshape(B, K, Hkv, g, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv * K * g, D)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(D), block_size=bs,
        block_pages=block_pages, table_width=W, group=g, k_tokens=K)
    rows = pl.BlockSpec((1, Hkv * K * g, D),
                        lambda b, ln, ly, tb: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            rows,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((2, block_pages * bs, Hkv, D), k_pages.dtype),
            pltpu.VMEM((2, block_pages * bs, Hkv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hkv * K * g, D), jnp.float32),
            pltpu.VMEM((Hkv * K * g, 1), jnp.float32),
            pltpu.VMEM((Hkv * K * g, 1), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv * K * g, D), q.dtype),
        # rows run in order: each row's last block starts the next's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      tables.astype(jnp.int32).reshape(B * W), qg, k_pages, v_pages)
    out = out.reshape(B, Hkv, K, g, D).transpose(0, 2, 1, 3, 4) \
             .reshape(B, K, H, D)
    return out[:, 0] if squeeze else out
