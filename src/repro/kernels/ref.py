"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, Hkv, S, D). Dense softmax attention."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Sq, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf) / math.sqrt(D)
    if causal:
        Sk = k.shape[2]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", w, vf)
    return out.reshape(B, H, Sq, D).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, layer, tables: jax.Array,
                        lengths: jax.Array) -> jax.Array:
    """Gather-decode/verify oracle over layer ``layer`` of a stacked
    paged KV pool (the jnp twin the models use off-TPU).

    q: (B, H, D) single-token decode, or (B, K, H, D) for a K-token
    verify step (speculative decoding: the K queries of one row are
    consecutive positions of the same request); k_pages/v_pages:
    (layers, P, bs, Hkv, D); layer: int32 scalar; tables: (B, W) int32
    physical page ids; lengths:
    (B,) valid KV tokens for the FIRST query of each row — query t of a
    row sees ``lengths[b] + t`` tokens, the intra-block causal
    staircase.  Returns the same rank as ``q``.  Gathers each row's
    pages into logical order and runs masked attention; HBM traffic is
    O(B * W * bs) — the Pallas kernel performs the same gather
    page-by-page in VMEM.  The gather width W should be bucketed by the
    caller to the batch's true maximum page count (the scheduler
    additionally GROUPS rows by pow2 width so one long request does not
    widen every row's gather on CPU).
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, K, H, D = q.shape
    _, _, bs, Hkv, _ = k_pages.shape
    W = tables.shape[1]
    g = H // Hkv
    kg = k_pages[layer, tables].reshape(B, W * bs, Hkv, D) \
        .astype(jnp.float32)
    vg = v_pages[layer, tables].reshape(B, W * bs, Hkv, D) \
        .astype(jnp.float32)
    qg = q.reshape(B, K, Hkv, g, D).astype(jnp.float32)
    s = jnp.einsum("bthgd,bkhd->bthgk", qg, kg) / math.sqrt(D)
    pos = jnp.arange(W * bs, dtype=jnp.int32)
    lens = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    valid = pos[None, None, :] < lens[..., None]         # (B, K, W*bs)
    s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bthgk,bkhd->bthgd", w, vg)
    out = out.reshape(B, K, H, D).astype(q.dtype)
    return out[:, 0] if squeeze else out


def latent_paged_attention_ref(q: jax.Array, pool: jax.Array, layer,
                               tables: jax.Array, lengths: jax.Array,
                               scale: float, value_dim: int) -> jax.Array:
    """Oracle of the latent paged decode kernel (the jnp twin the models
    use off-TPU).

    q: (B, H, D) or (B, K, H, D) absorbed queries; pool: (layers, P+1,
    bs, D) stacked latent pool; layer: int32 scalar; tables: (B, W);
    lengths: (B,) tokens the first query of each row sees (query t sees
    ``lengths[b] + t``; a row of length 0 returns zeros).  Scores over
    all D columns of the gathered rows, values their first
    ``value_dim``; float32 out.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, K, H, D = q.shape
    bs = pool.shape[2]
    W = tables.shape[1]
    rows = pool[layer][tables].reshape(B, W * bs, D).astype(jnp.float32)
    s = jnp.einsum("bthd,bkd->bthk", q.astype(jnp.float32), rows) * scale
    pos = jnp.arange(W * bs, dtype=jnp.int32)
    lens = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    valid = pos[None, None, :] < lens[..., None]          # (B, K, W*bs)
    s = jnp.where(valid[:, :, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid[:, :, None, :],
                  jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    w = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bthk,bkl->bthl", w, rows[..., :value_dim])
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out[:, 0] if squeeze else out


def rmsnorm_ref(x: jax.Array, scale: jax.Array,
                eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def mamba_scan_ref(dt: jax.Array, xc: jax.Array, bm: jax.Array,
                   cm: jax.Array, a: jax.Array) -> jax.Array:
    """Sequential selective-scan oracle.

    dt/xc: (B,S,d); bm/cm: (B,S,N); a: (d,N) -> y: (B,S,d)."""
    B, S, d = dt.shape
    N = a.shape[1]

    def step(h, inputs):
        dt_t, xc_t, bm_t, cm_t = inputs              # (B,d),(B,d),(B,N),(B,N)
        da = jnp.exp(dt_t[..., None] * a)            # (B,d,N)
        dbx = (dt_t * xc_t)[..., None] * bm_t[:, None, :]
        h1 = da * h + dbx
        y = jnp.einsum("bdn,bn->bd", h1, cm_t)
        return h1, y

    h0 = jnp.zeros((B, d, N), jnp.float32)
    _, ys = jax.lax.scan(
        step, h0,
        (dt.swapaxes(0, 1), xc.swapaxes(0, 1),
         bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
    return ys.swapaxes(0, 1)


def slstm_ref(gx: jax.Array, r_h: jax.Array, num_heads: int) -> jax.Array:
    """Sequential sLSTM oracle (stabilized exponential gating).

    gx: (B,S,4d) input gates [i|f|z|o]; r_h: (H, dh, 4dh) block-diagonal
    recurrent weights -> h: (B,S,d)."""
    B, S, d4 = gx.shape
    d = d4 // 4
    H = num_heads
    dh = d // H

    def step(state, g):
        h0, c0, n0, m0 = state
        rec = jnp.einsum("bhd,hde->bhe", h0.reshape(B, H, dh), r_h)
        rec = rec.reshape(B, H, 4, dh).transpose(0, 2, 1, 3) \
                 .reshape(B, 4 * d)
        gates = g + rec
        it, ft, zt, ot = jnp.split(gates, 4, axis=-1)
        lf = jax.nn.log_sigmoid(ft)
        m1 = jnp.maximum(lf + m0, it)
        ip = jnp.exp(it - m1)
        fp = jnp.exp(lf + m0 - m1)
        c1 = fp * c0 + ip * jnp.tanh(zt)
        n1 = jnp.maximum(fp * n0 + ip, 1e-6)
        h1 = jax.nn.sigmoid(ot) * c1 / n1
        return (h1, c1, n1, m1), h1

    z = jnp.zeros((B, d), jnp.float32)
    state0 = (z, z, z, jnp.full((B, d), -1e9, jnp.float32))
    _, hs = jax.lax.scan(step, state0, gx.swapaxes(0, 1))
    return hs.swapaxes(0, 1)
