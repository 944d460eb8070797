"""Pallas TPU fused sLSTM recurrence kernel.

The sLSTM is sequential over time — at the HLO level every timestep
re-reads the recurrent weights and state from HBM, which makes the
xlstm-125m roofline 99.5% sLSTM traffic (EXPERIMENTS.md §Perf).  The
xLSTM authors solved this with a fused CUDA kernel; this is the TPU
analogue (DESIGN.md §2 hardware adaptation):

  * grid = (batch_blocks, seq_chunks); the sequence dimension iterates
    sequentially (TPU grids are lexicographic), so the (h, c, n, m)
    state lives in VMEM scratch ACROSS chunk steps;
  * the block-diagonal per-head recurrent weights r_h (H, dh, 4dh) are
    small (<1 MB) and stay VMEM-resident for the whole sweep;
  * HBM traffic collapses to one read of the precomputed input gates
    gx = x W_x + b and one write of the outputs — the kernel-credit the
    roofline applies for the deployed configuration.

Inputs:  gx (B, S, 4d) f32 with gate layout [i|f|z|o], r_h (H, dh, 4dh)
Outputs: h  (B, S, d) f32
Oracle:  repro.kernels.ref.slstm_ref (== models.xlstm scan path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(gx_ref, r_ref, o_ref, h_ref, c_ref, n_ref, m_ref, *,
            chunk: int, num_heads: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e9)

    r = r_ref[...]                                 # (H, dh, 4dh)
    bb, d = h_ref.shape
    H = num_heads
    dh = d // H

    def step(t, _):
        h0 = h_ref[...]
        c0 = c_ref[...]
        n0 = n_ref[...]
        m0 = m_ref[...]
        rec = jax.lax.dot_general(
            h0.reshape(bb, H, dh), r,
            (((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32)    # (H, bb, 4dh)
        rec = rec.transpose(1, 0, 2).reshape(bb, H, 4, dh) \
                 .transpose(0, 2, 1, 3).reshape(bb, 4 * d)
        gates = gx_ref[:, t, :] + rec
        it = gates[:, 0 * d:1 * d]
        ft = gates[:, 1 * d:2 * d]
        zt = gates[:, 2 * d:3 * d]
        ot = gates[:, 3 * d:4 * d]
        lf = -jax.nn.softplus(-ft)                 # log sigmoid
        m1 = jnp.maximum(lf + m0, it)
        ip = jnp.exp(it - m1)
        fp = jnp.exp(lf + m0 - m1)
        c1 = fp * c0 + ip * jnp.tanh(zt)
        n1 = jnp.maximum(fp * n0 + ip, 1e-6)
        h1 = jax.nn.sigmoid(ot) * c1 / n1
        h_ref[...] = h1
        c_ref[...] = c1
        n_ref[...] = n1
        m_ref[...] = m1
        o_ref[:, t, :] = h1
        return ()

    jax.lax.fori_loop(0, chunk, step, ())


def slstm_scan(gx: jax.Array, r_h: jax.Array, block_b: int = 8,
               chunk: int = 128, interpret: bool = False) -> jax.Array:
    """gx: (B, S, 4d) f32; r_h: (H, dh, 4dh) f32 -> h: (B, S, d) f32."""
    B, S, d4 = gx.shape
    d = d4 // 4
    H = r_h.shape[0]
    block_b = min(block_b, B)
    while B % block_b:
        block_b -= 1
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    grid = (B // block_b, S // chunk)

    scratch = [pltpu.VMEM((block_b, d), jnp.float32) for _ in range(4)]

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, num_heads=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, chunk, d4), lambda i, j: (i, j, 0)),
            pl.BlockSpec((H, d // H, 4 * (d // H)), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, chunk, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(gx, r_h)
