"""Pallas TPU flash attention (causal, GQA) — the VMEM-resident kernel
whose pure-JAX twin is ``repro.models.layers.chunked_attention``.

TPU adaptation of the CUDA flash-attention idea (DESIGN.md §2/§6):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the KV-block dimension
    is innermost so the (block_q, head_dim) accumulator lives in VMEM
    scratch across the KV sweep — HBM traffic is exactly Q, K, V reads +
    O writes (what the roofline credits as the kernel-deployed memory
    term);
  * block shapes are MXU-aligned (multiples of 128 on the matmul dims —
    block_q x head_dim tiles hit the 128x128 systolic array);
  * GQA is expressed in the K/V BlockSpec index_map (q head h reads kv
    head h // group), so no KV duplication is materialized;
  * causal masking skips fully-masked KV blocks via ``pl.when``.

Numerics: online softmax with running (m, l) in f32 scratch, inputs may
be bf16/f32; output is cast back to the query dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        # skip blocks strictly above the diagonal
        run = (ki * block_k) <= (qi * block_q + block_q - 1)
    else:
        run = ki >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, Hkv, S, D) with H % Hkv == 0.

    Returns (B, H, S, D) attention output.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0
    g = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (
        f"seq lens ({Sq},{Sk}) must tile by ({block_q},{block_k})")
    nq, nk = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(D)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, num_k_blocks=nk)

    scratch = [
        pltpu.VMEM((block_q, D), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
    ]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
