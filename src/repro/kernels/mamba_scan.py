"""Pallas TPU selective-scan (Mamba) kernel.

Jamba's remaining roofline memory term is the chunked selective scan:
at the HLO level each chunk materializes (B, Q, d_in, N) discretized-SSM
tensors in HBM.  The fused kernel keeps the (block_d, N) state and the
per-step (block_d, N) discretization products in VMEM — HBM traffic
collapses to reading (dt, xc) and writing y (+ the small B/C mats),
the same adaptation the CUDA selective-scan kernel makes on GPU
(DESIGN.md §2).

Grid: (batch, d_blocks, seq_chunks); the sequence dimension iterates
sequentially so the state scratch persists across chunks.

Inputs (all f32):
  dt  (B, S, d_in)  — post-softplus step sizes
  xc  (B, S, d_in)  — post-conv/silu activations
  Bm  (B, S, N)     — input projections
  Cm  (B, S, N)     — output projections
  A   (d_in, N)     — negative state matrix
Output: y (B, S, d_in) with y[t] = C[t] . h[t],
  h[t] = exp(dt[t] A) h[t-1] + (dt[t] xc[t]) B[t].
Oracle: repro.kernels.ref.mamba_scan_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(dt_ref, xc_ref, bm_ref, cm_ref, a_ref, y_ref, h_ref, *,
            chunk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]                                   # (bd, N)

    def step(t, _):
        h = h_ref[...]                               # (1, bd, N)
        dt = dt_ref[0, t, :]                         # (bd,)
        xc = xc_ref[0, t, :]
        bm = bm_ref[0, t, :]                         # (N,)
        cm = cm_ref[0, t, :]
        da = jnp.exp(dt[:, None] * a)                # (bd, N)
        dbx = (dt * xc)[:, None] * bm[None, :]       # (bd, N)
        h1 = da * h[0] + dbx
        h_ref[...] = h1[None]
        y_ref[0, t, :] = h1 @ cm                     # (bd,)
        return ()

    jax.lax.fori_loop(0, chunk, step, ())


def mamba_scan(dt: jax.Array, xc: jax.Array, bm: jax.Array, cm: jax.Array,
               a: jax.Array, block_d: int = 512, chunk: int = 128,
               interpret: bool = False) -> jax.Array:
    """Fused selective scan. Shapes per module docstring."""
    B, S, d_in = dt.shape
    N = a.shape[1]
    block_d = min(block_d, d_in)
    while d_in % block_d:
        block_d -= 1
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    grid = (B, d_in // block_d, S // chunk)

    scratch = [pltpu.VMEM((1, block_d, N), jnp.float32)]

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, i, k: (b, k, i)),
            pl.BlockSpec((1, chunk, block_d), lambda b, i, k: (b, k, i)),
            pl.BlockSpec((1, chunk, N), lambda b, i, k: (b, k, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, i, k: (b, k, 0)),
            pl.BlockSpec((block_d, N), lambda b, i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda b, i, k: (b, k, i)),
        out_shape=jax.ShapeDtypeStruct((B, S, d_in), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(dt, xc, bm, cm, a)
