"""Core transformer building blocks (pure-functional JAX).

Every ``init_*`` function returns ``(params, axes)`` where ``axes`` is a
pytree of logical-axis tuples parallel to ``params`` (consumed by
``repro.parallel.sharding.tree_shardings`` for FSDP/TP/EP placement).

All forward functions are shape-polymorphic over batch/seq and annotate
activations with ``constrain`` so GSPMD propagates DP/TP/SP shardings.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.sharding import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


class KeyGen:
    """Sequential PRNG key dispenser."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    # GPT-style small init: keeps tied-head logits O(1) at initialization
    return (jax.random.normal(key, (vocab, d), jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> Tuple[Params, Params]:
    return {"scale": jnp.ones((d,), dtype)}, {"scale": (None,)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                     # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, D); positions: (3, B, S) — (temporal, height, width)
    components.  ``sections`` partitions the D/2 rotary frequencies among
    the three components.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                               # (D/2,)
    # section id per frequency
    sec = jnp.concatenate([
        jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)])
    assert sec.shape[0] == d // 2, (sections, d)
    pos = positions.astype(jnp.float32)                        # (3,B,S)
    pos_per_freq = jnp.take(pos, sec, axis=0)                  # (D/2,B,S)
    angles = jnp.transpose(pos_per_freq, (1, 2, 0)) * freqs    # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def default_positions(batch: int, seq: int, offset=0) -> jax.Array:
    return jnp.arange(seq, dtype=jnp.int32)[None, :] + offset \
        + jnp.zeros((batch, 1), jnp.int32)


# ---------------------------------------------------------------------------
# Attention (GQA + RoPE/M-RoPE + qk-norm + optional bias + KV cache)
# ---------------------------------------------------------------------------


def init_attention(keys: KeyGen, cfg: ModelConfig) -> Tuple[Params, Params]:
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    p: Params = {
        "wq": dense_init(keys(), d, cfg.q_dim, dt),
        "wk": dense_init(keys(), d, cfg.kv_dim, dt),
        "wv": dense_init(keys(), d, cfg.kv_dim, dt),
        "wo": dense_init(keys(), cfg.q_dim, d, dt),
    }
    a: Params = {
        "wq": ("embed", "heads_w"),
        "wk": ("embed", "heads_w"),
        "wv": ("embed", "heads_w"),
        "wo": ("heads_w", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
        a["bq"] = ("heads_w",)
        a["bk"] = ("heads_w",)
        a["bv"] = ("heads_w",)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
        a["q_norm"] = (None,)
        a["k_norm"] = (None,)
    return p, a


def _project_qkv(params: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": params["k_norm"]}, k, cfg.norm_eps)
    if cfg.use_mrope:
        sec = cfg.frontend.mrope_sections
        q = apply_mrope(q, positions, cfg.rope_theta, sec)
        k = apply_mrope(k, positions, cfg.rope_theta, sec)
    else:
        if positions.ndim == 3:       # (3,B,S) given but plain rope
            positions = positions[0]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """Reference grouped-query attention. q: (B,S,H,D), k/v: (B,S,Hkv,D).
    Materializes the (S, S) score matrix — short sequences only."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    q = q.reshape(B, S, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return out.reshape(B, S, H, D)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, k_chunk: int = 1024) -> jax.Array:
    """Flash-style attention: online softmax over KV chunks via lax.scan.

    The pure-JAX twin of ``kernels/flash_attention.py`` — never
    materializes the (S, S) score matrix in HBM (peak extra memory is one
    (B, S, H, k_chunk) block), which is what makes prefill_32k lowerable
    and is the memory-roofline optimization the Pallas kernel performs in
    VMEM on real TPUs.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    Sk = k.shape[1]
    C = min(k_chunk, Sk)
    while Sk % C:
        C -= 1
    n_chunks = Sk // C
    scale = 1.0 / math.sqrt(D)
    qg = (q * scale).reshape(B, S, Hkv, g, D).astype(jnp.float32)
    kc = k.reshape(B, n_chunks, C, Hkv, D).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, C, Hkv, D).swapaxes(0, 1)
    q_pos = jnp.arange(S, dtype=jnp.int32)

    def step(carry, inp):
        acc, m, l = carry                     # (B,S,Hkv,g,D), (B,S,Hkv,g)x2
        kb, vb, ci = inp                      # (B,C,Hkv,D), (B,C,Hkv,D), ()
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb.astype(jnp.float32))
        if causal:
            kv_pos = ci * C + jnp.arange(C, dtype=jnp.int32)
            mask = q_pos[:, None] >= kv_pos[None, :]     # (S, C)
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, S, Hkv, g, D), jnp.float32)
    m0 = jnp.full((B, S, Hkv, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, g), jnp.float32)
    with jax.named_scope("flash_attention"):
        (acc, m, l), _ = jax.lax.scan(
            step, (acc0, m0, l0),
            (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash attention with custom VJP (training-memory-correct)
#
# Differentiating through the online-softmax scan would make scan-carry
# residuals O(S * n_chunks); instead we save only (q, k, v, out, lse) and
# run the textbook flash-attention backward as a second chunked scan —
# exactly what the Pallas kernel does on TPU (kernels/flash_attention.py
# is the forward; its backward twin shares this structure).
# ---------------------------------------------------------------------------


def _flash_fwd_scan(q, k, v, causal: bool, k_chunk: int):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    Sk = k.shape[1]
    C = min(k_chunk, Sk)
    while Sk % C:
        C -= 1
    n_chunks = Sk // C
    scale = 1.0 / math.sqrt(D)
    qg = (q.astype(jnp.float32) * scale).reshape(B, S, Hkv, g, D)
    kc = k.reshape(B, n_chunks, C, Hkv, D).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, C, Hkv, D).swapaxes(0, 1)
    q_pos = jnp.arange(S, dtype=jnp.int32)

    def step(carry, inp):
        acc, m, l = carry
        kb, vb, ci = inp
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb.astype(jnp.float32))
        if causal:
            kv_pos = ci * C + jnp.arange(C, dtype=jnp.int32)
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, S, Hkv, g, D), jnp.float32)
    m0 = jnp.full((B, S, Hkv, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, g), jnp.float32)
    with jax.named_scope("flash_attention"):
        (acc, m, l), _ = jax.lax.scan(
            step, (acc0, m0, l0),
            (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32)))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None]).reshape(B, S, H, D).astype(q.dtype)
    lse = jnp.where(jnp.isfinite(m), m + jnp.log(l), -jnp.inf)  # (B,S,h,g)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_jax(q, k, v, causal: bool = True, k_chunk: int = 1024):
    out, _ = _flash_fwd_scan(q, k, v, causal, k_chunk)
    return out


def _flash_vjp_fwd(q, k, v, causal, k_chunk):
    out, lse = _flash_fwd_scan(q, k, v, causal, k_chunk)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, k_chunk, res, dout):
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    Sk = k.shape[1]
    C = min(k_chunk, Sk)
    while Sk % C:
        C -= 1
    n_chunks = Sk // C
    scale = 1.0 / math.sqrt(D)
    qg = q.astype(jnp.float32).reshape(B, S, Hkv, g, D)
    og = out.astype(jnp.float32).reshape(B, S, Hkv, g, D)
    dog = dout.astype(jnp.float32).reshape(B, S, Hkv, g, D)
    delta = jnp.sum(og * dog, axis=-1)                    # (B,S,h,g)
    kc = k.reshape(B, n_chunks, C, Hkv, D).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, C, Hkv, D).swapaxes(0, 1)
    q_pos = jnp.arange(S, dtype=jnp.int32)

    def step(dq, inp):
        kb, vb, ci = inp
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg * scale,
                       kb.astype(jnp.float32))
        if causal:
            kv_pos = ci * C + jnp.arange(C, dtype=jnp.int32)
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
        p = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - lse_safe[..., None]), 0.0)  # (B,S,h,g,C)
        dv_c = jnp.einsum("bqhgk,bqhgd->bkhd", p, dog)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", dog, vb.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds,
                             kb.astype(jnp.float32))
        dk_c = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qg)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((B, S, Hkv, g, D), jnp.float32)
    with jax.named_scope("flash_attention_bwd"):
        dq, (dk_c, dv_c) = jax.lax.scan(
            step, dq0, (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32)))
    dk = dk_c.swapaxes(0, 1).reshape(B, Sk, Hkv, D).astype(k.dtype)
    dv = dv_c.swapaxes(0, 1).reshape(B, Sk, Hkv, D).astype(v.dtype)
    return (dq.reshape(B, S, H, D).astype(q.dtype), dk, dv)


flash_attention_jax.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool = True, impl: str = "auto",
                     k_chunk: int = 1024) -> jax.Array:
    if impl == "dense" or (impl == "auto" and q.shape[1] < 4096):
        return dense_attention(q, k, v, causal)
    return flash_attention_jax(q, k, v, causal, k_chunk)


def attention_block(params: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array) -> jax.Array:
    """Full-sequence causal attention (training / prefill)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = causal_attention(q, k, v, impl=cfg.attn_impl,
                           k_chunk=cfg.attn_chunk)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(B, S, cfg.q_dim) @ params["wo"]
    return constrain(out, "batch", "seq", "act_embed")


def attention_prefill(params: Params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array):
    """Like attention_block but also returns the (K, V) cache."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = causal_attention(q, k, v, impl=cfg.attn_impl,
                           k_chunk=cfg.attn_chunk)
    out = out.reshape(B, S, cfg.q_dim) @ params["wo"]
    cache = {
        "k": constrain(k, "batch", "kv_seq", "kv_heads", "head_dim"),
        "v": constrain(v, "batch", "kv_seq", "kv_heads", "head_dim"),
    }
    return constrain(out, "batch", "seq", "act_embed"), cache


def attention_decode(params: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Params, index: jax.Array,
                     positions: jax.Array):
    """K-token decode/verify with a KV cache of static length S_max.

    x: (B, K, d) — K >= 1 consecutive tokens per row (K = 1 is the
    classic decode step; K > 1 is the speculative-verify write/read);
    cache['k'/'v']: (B, S_max, Hkv, D); index: scalar int32 write
    position of the FIRST token (= current KV length), or an int32 (B,)
    vector of per-row write positions (continuous batching: each cache
    row belongs to a different request at a different length).  Token t
    of row b is written at ``index[b] + t`` and attends causally over
    positions ``<= index[b] + t``.  Writes past S_max are dropped —
    they can only be speculative padding the caller rolls back.
    Returns (out, new_cache).
    """
    B, K, _ = x.shape
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    index = jnp.asarray(index, jnp.int32)
    if index.ndim == 0 and K == 1:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), index, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), index, axis=1)
    else:
        # per-(row, token) write: scatter K (Hkv, D) rows per batch
        # element — O(B*K*Hkv*D) traffic, independent of max_len
        idx_col = index[:, None] if index.ndim else \
            jnp.full((B, 1), index, jnp.int32)
        wpos = idx_col + jnp.arange(K, dtype=jnp.int32)[None, :]  # (B,K)
        rows = jnp.broadcast_to(
            jnp.arange(B, dtype=jnp.int32)[:, None], (B, K))
        k = cache["k"].at[rows, wpos].set(
            k_new.astype(cache["k"].dtype), mode="drop")
        v = cache["v"].at[rows, wpos].set(
            v_new.astype(cache["v"].dtype), mode="drop")
    k = constrain(k, "batch", "kv_seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "kv_seq", "kv_heads", "head_dim")
    S_max = k.shape[1]
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = H // Hkv
    qh = q.reshape(B, K, Hkv, g, D)
    scores = jnp.einsum("bthgd,bkhd->bthgk", qh, k,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
    pos = jnp.arange(S_max, dtype=jnp.int32)
    reach = (index if index.ndim else jnp.full((B,), index, jnp.int32))[
        :, None] + jnp.arange(K, dtype=jnp.int32)[None, :]      # (B, K)
    valid = pos[None, None, :] <= reach[..., None]              # (B,K,S)
    scores = jnp.where(valid[:, :, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bthgk,bkhd->bthgd", w.astype(v.dtype), v)
    out = out.reshape(B, K, cfg.q_dim) @ params["wo"]
    return constrain(out, "batch", "seq", "act_embed"), {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Paged attention (serving): KV lives in a shared page pool, requests
# address it through per-row block tables.  The gather-decode compute is
# kernels/paged_attention.py on TPU and kernels/ref.paged_attention_ref
# (the jnp twin) everywhere else.
# ---------------------------------------------------------------------------


def init_paged_attention_pool(cfg: ModelConfig, layers: int,
                              num_pages: int, block_size: int):
    """``layers`` paged KV pools stacked from the start: K and V each
    (layers, num_pages + 1, block_size, Hkv, D), allocated at that shape
    (no per-layer pools to stack).  Returns (pool, the axes of ONE
    layer's pool); the caller names the stacking axis.

    The extra page (index ``num_pages``) is the NULL page: inactive
    batch rows and dropped (padded) prefill positions write there, so a
    row that owns no pages can never corrupt another request's cache.
    """
    dt = jnp.dtype(cfg.dtype)
    shape = (layers, num_pages + 1, block_size, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    axes = {"k": ("pages", None, "kv_heads", "head_dim"),
            "v": ("pages", None, "kv_heads", "head_dim")}
    return cache, axes


def _scatter_pages(pool: jax.Array, layer, vals: jax.Array,
                   page_ids: jax.Array, offsets: jax.Array) -> jax.Array:
    """Write vals[n] -> pool[layer, page_ids[n], offsets[n]] (rows of
    (Hkv, D)), in place in the stacked pool."""
    return pool.at[layer, page_ids, offsets].set(vals.astype(pool.dtype))


def attention_decode_paged(params: Params, cfg: ModelConfig, x: jax.Array,
                           cache: Params, layer: jax.Array,
                           index: jax.Array, positions: jax.Array,
                           tables: jax.Array,
                           valid: Optional[jax.Array] = None):
    """K-token decode/verify against layer ``layer`` of the stacked
    paged pool.

    x: (B, K, d) — K >= 1 consecutive tokens per row; cache['k'/'v']:
    (layers, P+1, bs, Hkv, D) stacked shared pools, written in place
    and read by the kernel at ``layer`` (carried through the layer
    scan, never sliced out); index: int32 (B,) per-row write
    position of the FIRST token, with -1 marking inactive rows (their
    KV is routed to the null page and their output is garbage the
    caller discards); tables: (B, W) int32 physical page ids; valid:
    optional int32 (B,) count of real tokens per row — tokens t >=
    valid[b] (speculative padding / replay no-ops) scatter to the null
    page so they can never corrupt a page the row does not own yet.
    Token t writes at ``index[b] + t`` and attends causally over
    positions ``<= index[b] + t``.  Returns (out, new_cache).
    """
    from repro.kernels import ops

    B, K, _ = x.shape
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    bs = cache["k"].shape[2]
    null_page = cache["k"].shape[1] - 1
    W = tables.shape[1]
    index = jnp.asarray(index, jnp.int32)
    active = (index >= 0)[:, None]                        # (B, 1)
    if valid is not None:
        active = active & (jnp.arange(K, dtype=jnp.int32)[None, :]
                           < jnp.asarray(valid, jnp.int32)[:, None])
    widx = jnp.maximum(index, 0)
    wpos = widx[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]  # (B,K)
    page = jnp.take_along_axis(tables, jnp.minimum(wpos // bs, W - 1),
                               axis=1)
    page = jnp.where(active, page, null_page)
    off = wpos % bs
    k = _scatter_pages(cache["k"], layer,
                       k_new.reshape(B * K, *k_new.shape[2:]),
                       page.reshape(-1), off.reshape(-1))
    v = _scatter_pages(cache["v"], layer,
                       v_new.reshape(B * K, *v_new.shape[2:]),
                       page.reshape(-1), off.reshape(-1))
    lengths = widx + 1                       # KV tokens seen by query 0
    # the compiled Pallas kernel on TPU, the jnp oracle elsewhere
    out = ops.paged_attention(q, k, v, layer, tables, lengths)
    out = out.reshape(B, K, cfg.q_dim) @ params["wo"]
    return constrain(out, "batch", "seq", "act_embed"), {"k": k, "v": v}


def attention_chunk_paged(params: Params, cfg: ModelConfig, x: jax.Array,
                          cache: Params, layer: jax.Array,
                          tables: jax.Array, hist_len: jax.Array,
                          prompt_len: jax.Array, positions: jax.Array):
    """One chunked-prefill step for a single request over layer
    ``layer`` of the stacked paged pool.

    x: (1, C, d) — the prompt slice [hist_len, hist_len + C) (the tail
    chunk may be right-padded past ``prompt_len``; padded positions
    scatter to the null page and are causally invisible to real
    queries); cache: stacked shared (layers, P+1, bs, Hkv, D) pools,
    written and gathered in place; tables: (1, W)
    this request's block-table row; hist_len/prompt_len: int32 scalars.
    Chunk KV is scattered into the pool first, then the chunk queries
    attend over the gathered pages — which covers both the already-
    prefilled history (including prefix-shared pages) and the chunk
    itself under one causal mask.  Returns (out, new_cache).
    """
    B, C, _ = x.shape
    assert B == 1
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    bs = cache["k"].shape[2]
    null_page = cache["k"].shape[1] - 1
    W = tables.shape[1]
    abs_pos = jnp.asarray(hist_len, jnp.int32) \
        + jnp.arange(C, dtype=jnp.int32)
    valid = abs_pos < prompt_len
    page = jnp.take(tables[0], jnp.minimum(abs_pos // bs, W - 1))
    page = jnp.where(valid, page, null_page)
    off = abs_pos % bs
    k = _scatter_pages(cache["k"], layer, k_new[0], page, off)
    v = _scatter_pages(cache["v"], layer, v_new[0], page, off)

    Hkv, D = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // Hkv
    kg = k[layer, tables[0]].reshape(W * bs, Hkv, D).astype(jnp.float32)
    vg = v[layer, tables[0]].reshape(W * bs, Hkv, D).astype(jnp.float32)
    qg = q[0].reshape(C, Hkv, g, D).astype(jnp.float32)
    s = jnp.einsum("qhgd,khd->hgqk", qg, kg) / math.sqrt(D)
    kv_pos = jnp.arange(W * bs, dtype=jnp.int32)
    mask = kv_pos[None, :] <= abs_pos[:, None]            # causal, absolute
    s = jnp.where(mask[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hgqk,khd->qhgd", w, vg).astype(x.dtype)
    out = out.reshape(1, C, cfg.q_dim) @ params["wo"]
    return constrain(out, "batch", "seq", "act_embed"), {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
#
# Per token: q = W_q x, split per head into q_nope and q_pe;
# [c_kv; k_pe] = W_kva x; c_kv is RMS-normalised (kv_a_layernorm) and
# expanded per head to [k_nope; v] = W_kvb c_kv; k_pe is ONE rotary key
# head shared by every head.  Scores are q_nope.k_nope + q_pe.k_pe over
# sqrt(qk_head_dim), times YaRN's mscale^2.  The serving cache holds only
# [c_kv; k_pe] (latent_dim values a token a layer), so decode runs the
# absorbed form: q_nope is mapped into the latent space through W_UK (the
# k_nope half of W_kvb), scores are taken against the cached rows, the
# softmax-weighted c_kv is mapped back through W_UV, then o_proj.
# ---------------------------------------------------------------------------


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def mla_rope_freqs(cfg: ModelConfig) -> jax.Array:
    """Inverse frequencies of the rotary dims: YaRN's blend of the
    original and the ``factor``-interpolated frequencies, ramped between
    the correction dims of ``beta_fast`` and ``beta_slow`` rotations."""
    dim = cfg.mla.qk_rope_head_dim
    base = cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    y = cfg.yarn
    if y is None:
        return extra

    def corr_dim(rot):
        return dim * math.log(y.original_max_position
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp           # 1: the original frequency, 0: interpolated
    return extra / y.factor * (1.0 - keep) + extra * keep


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2`` when the rope is scaled."""
    scale = cfg.mla.qk_head_dim ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        m = _yarn_mscale(y.factor, y.mscale_all_dim)
        scale *= m * m
    return scale


def apply_mla_rope(x: jax.Array, positions: jax.Array,
                   cfg: ModelConfig) -> jax.Array:
    """DeepSeek's rotary convention: the rope dims hold interleaved pairs
    (x[2i], x[2i+1]); they are de-interleaved to [evens; odds] and then
    rotated half against half (``rotate_half``), so pair i turns by
    ``position * inv_freq[i]``.  x: (B, S, ..., r); positions: (B, S)."""
    B, S = x.shape[:2]
    r = x.shape[-1]
    y = cfg.yarn
    ms = 1.0 if y is None else (_yarn_mscale(y.factor, y.mscale)
                                / _yarn_mscale(y.factor, y.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * mla_rope_freqs(cfg)
    extra = (1,) * (x.ndim - 3)
    cos = (jnp.cos(ang) * ms).reshape(B, S, *extra, r // 2)
    sin = (jnp.sin(ang) * ms).reshape(B, S, *extra, r // 2)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def init_mla(keys: KeyGen, cfg: ModelConfig) -> Tuple[Params, Params]:
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    dt = jnp.dtype(cfg.dtype)
    p: Params = {
        "wq": dense_init(keys(), d, H * m.qk_head_dim, dt),
        "wkv_a": dense_init(keys(), d, m.latent_dim, dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "wkv_b": dense_init(keys(), m.kv_lora_rank,
                            H * (m.qk_nope_head_dim + m.v_head_dim), dt),
        "wo": dense_init(keys(), H * m.v_head_dim, d, dt),
    }
    a: Params = {"wq": ("embed", "heads_w"), "wkv_a": ("embed", None),
                 "kv_norm": (None,), "wkv_b": (None, "heads_w"),
                 "wo": ("heads_w", "embed")}
    return p, a


def _mla_project(params: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array):
    """x (B, S, d) -> q_nope (B, S, H, n), roped q_pe (B, S, H, r) and
    the cache rows (B, S, latent_dim) = [rms(c_kv); roped k_pe]."""
    m, H = cfg.mla, cfg.num_heads
    B, S, _ = x.shape
    if positions.ndim == 3:       # (3, B, S) given but plain rope
        positions = positions[0]
    q = (x @ params["wq"]).reshape(B, S, H, m.qk_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_pe = apply_mla_rope(q[..., m.qk_nope_head_dim:], positions, cfg)
    kv = x @ params["wkv_a"]
    c = rmsnorm({"scale": params["kv_norm"]}, kv[..., :m.kv_lora_rank],
                cfg.norm_eps)
    k_pe = apply_mla_rope(kv[..., m.kv_lora_rank:], positions, cfg)
    return q_nope, q_pe, jnp.concatenate([c, k_pe], -1)


def _mla_kv_b(params: Params, cfg: ModelConfig):
    """W_kvb as (rank, H, n + v): W_UK = [..., :n], W_UV = [..., n:]."""
    m = cfg.mla
    return params["wkv_b"].reshape(m.kv_lora_rank, cfg.num_heads,
                                   m.qk_nope_head_dim + m.v_head_dim)


def mla_block(params: Params, cfg: ModelConfig, x: jax.Array,
              positions: jax.Array, return_cache: bool = False):
    """Full (non-absorbed) causal latent attention over the whole
    sequence, as published (training / full prefill).  With
    ``return_cache`` also returns the cache rows {"c": (B, S, pool_dim)}
    (zero-padded as the pool holds them)."""
    m = cfg.mla
    B, S, _ = x.shape
    with jax.named_scope("mla"):
        q_nope, q_pe, lat = _mla_project(params, cfg, x, positions)
        c, k_pe = lat[..., :m.kv_lora_rank], lat[..., m.kv_lora_rank:]
        kv = jnp.einsum("bsl,lhe->bshe", c, _mla_kv_b(params, cfg))
        k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
        s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                          preferred_element_type=jnp.float32))
        s = s * mla_softmax_scale(cfg)
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhv->bqhv", w.astype(v.dtype), v)
        out = out.reshape(B, S, -1) @ params["wo"]
    out = constrain(out, "batch", "seq", "act_embed")
    if return_cache:
        return out, {"c": _pool_rows(lat, cfg)}
    return out


def _mla_absorb_query(params: Params, cfg: ModelConfig, q_nope, q_pe):
    """[q_nope . W_UK; q_pe] (.., H, pool_dim) in float32: the query in
    the pool rows' space (zero over their padding)."""
    m = cfg.mla
    w_uk = _mla_kv_b(params, cfg)[..., :m.qk_nope_head_dim]
    q_lat = jnp.einsum("...hn,lhn->...hl", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return _pool_rows(jnp.concatenate([q_lat, q_pe.astype(jnp.float32)],
                                      -1), cfg)


def _mla_out(params: Params, cfg: ModelConfig, o_lat, dtype):
    """Softmax-weighted c_kv (.., H, rank) -> W_UV -> o_proj (.., d)."""
    m = cfg.mla
    w_uv = _mla_kv_b(params, cfg)[..., m.qk_nope_head_dim:]
    o = jnp.einsum("...hl,lhv->...hv", o_lat.astype(dtype), w_uv)
    return o.reshape(*o.shape[:-2], -1) @ params["wo"]


def _pool_rows(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rows (.., latent_dim) zero-padded to the pool's ``pool_dim``."""
    pad = cfg.mla.pool_dim - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def init_latent_pool(cfg: ModelConfig, layers: int, num_pages: int,
                     block_size: int):
    """``layers`` latent pools stacked from the start: (layers, num_pages
    + 1, block_size, pool_dim), page ``num_pages`` the null page."""
    shape = (layers, num_pages + 1, block_size, cfg.mla.pool_dim)
    return ({"c": jnp.zeros(shape, jnp.dtype(cfg.dtype))},
            {"c": ("layers", "pages", None, None)})


def mla_decode_paged(params: Params, cfg: ModelConfig, x: jax.Array,
                     pool: Params, layer: jax.Array, index: jax.Array,
                     positions: jax.Array, tables: jax.Array,
                     valid: Optional[jax.Array] = None):
    """K-token decode/verify in the absorbed form against layer ``layer``
    of the stacked latent pool {"c": (layers, P+1, bs, pool_dim)}.

    Rows, indices, tables and ``valid`` follow
    :func:`attention_decode_paged` (index -1: an idle row, its write goes
    to the null page).  The row's new cache entries are written in place
    into the stacked pool (carried, never sliced out), then
    :func:`repro.kernels.ops.latent_paged_attention` reads scores over
    the whole row and values over its kv_lora_rank columns, from the
    same pages.
    Returns (out, new pool).
    """
    from repro.kernels import ops

    B, K, _ = x.shape
    c = pool["c"]
    with jax.named_scope("mla"):
        q_nope, q_pe, lat = _mla_project(params, cfg, x, positions)
        bs = c.shape[2]
        null_page = c.shape[1] - 1
        W = tables.shape[1]
        index = jnp.asarray(index, jnp.int32)
        active = (index >= 0)[:, None]
        if valid is not None:
            active = active & (jnp.arange(K, dtype=jnp.int32)[None, :]
                               < jnp.asarray(valid, jnp.int32)[:, None])
        widx = jnp.maximum(index, 0)
        wpos = widx[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
        page = jnp.take_along_axis(tables, jnp.minimum(wpos // bs, W - 1),
                                   axis=1)
        page = jnp.where(active, page, null_page)
        c = c.at[layer, page.reshape(-1), (wpos % bs).reshape(-1)].set(
            _pool_rows(lat, cfg).reshape(B * K, -1).astype(c.dtype))
        q = _mla_absorb_query(params, cfg, q_nope, q_pe)
        # idle rows read nothing (length 0)
        lengths = jnp.where(index >= 0, widx + 1, 0)
        o_lat = ops.latent_paged_attention(
            q, c, layer, tables, lengths, scale=mla_softmax_scale(cfg),
            value_dim=cfg.mla.kv_lora_rank)
        out = _mla_out(params, cfg, o_lat, x.dtype)
    return constrain(out, "batch", "seq", "act_embed"), {"c": c}


# pages of keys a chunk's queries read per step of the online softmax
# (1,024 tokens: a (heads, chunk, 1024) float32 score block)
CHUNK_KEY_PAGES = 64


def latent_chunk_attention(q: jax.Array, pages: jax.Array, layer,
                           table: jax.Array, q_pos: jax.Array, scale: float,
                           value_dim: int) -> jax.Array:
    """Causal attention of C chunk queries over one request's pages.

    q: (C, H, D) float32 absorbed queries; pages: the stacked pool
    (layers, P+1, bs, D); table: (W,) the request's page ids; q_pos: (C,)
    absolute query positions (query i sees keys at positions <=
    q_pos[i]).  Keys are read ``CHUNK_KEY_PAGES`` pages at a time with
    an online softmax, and only the blocks some query reaches, so no (C,
    W * bs) score matrix is ever held.  Returns (C, H, value_dim) f32:
    the softmax-weighted first ``value_dim`` columns of the rows.
    """
    C, H, D = q.shape
    bs = pages.shape[2]
    W = table.shape[0]
    block_pages = CHUNK_KEY_PAGES
    nb = -(-W // block_pages)
    null_page = pages.shape[1] - 1
    table = jnp.pad(table, (0, nb * block_pages - W),
                    constant_values=null_page)
    tk = block_pages * bs
    n_live = jnp.minimum((jnp.max(q_pos) + tk) // tk, nb)

    def body(j, carry):
        acc, m_run, l_run = carry
        ids = jax.lax.dynamic_slice(table, (j * block_pages,),
                                    (block_pages,))
        kv = pages[layer, ids].reshape(tk, D).astype(jnp.float32)
        s = jnp.einsum("chd,td->hct", q, kv) * scale
        kpos = j * tk + jnp.arange(tk, dtype=jnp.int32)
        s = jnp.where((kpos[None, :] <= q_pos[:, None])[None], s, -jnp.inf)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0)
        acc = acc * corr[..., None] + jnp.einsum("hct,tl->hcl", p,
                                                 kv[:, :value_dim])
        return acc, m_new, l_run * corr + jnp.sum(p, axis=-1)

    init = (jnp.zeros((H, C, value_dim), jnp.float32),
            jnp.full((H, C), -jnp.inf, jnp.float32),
            jnp.zeros((H, C), jnp.float32))
    acc, _, l_run = jax.lax.fori_loop(0, n_live, body, init)
    return (acc / jnp.maximum(l_run, 1e-30)[..., None]).transpose(1, 0, 2)


def mla_chunk_paged(params: Params, cfg: ModelConfig, x: jax.Array,
                    pool: Params, layer: jax.Array, tables: jax.Array,
                    hist_len: jax.Array, prompt_len: jax.Array,
                    positions: jax.Array):
    """One chunked-prefill slice of a single request against layer
    ``layer`` of the stacked latent pool, in the absorbed form: the
    slice's cache rows are written first (padded positions to the null
    page), then its queries attend over the request's pages, history
    and slice under one causal mask (:func:`latent_chunk_attention`).
    Returns (out, new pool)."""
    B, C, _ = x.shape
    assert B == 1
    c = pool["c"]
    with jax.named_scope("mla"):
        q_nope, q_pe, lat = _mla_project(params, cfg, x, positions)
        bs = c.shape[2]
        null_page = c.shape[1] - 1
        W = tables.shape[1]
        abs_pos = jnp.asarray(hist_len, jnp.int32) \
            + jnp.arange(C, dtype=jnp.int32)
        page = jnp.take(tables[0], jnp.minimum(abs_pos // bs, W - 1))
        page = jnp.where(abs_pos < prompt_len, page, null_page)
        c = c.at[layer, page, abs_pos % bs].set(
            _pool_rows(lat[0], cfg).astype(c.dtype))
        q = _mla_absorb_query(params, cfg, q_nope[0], q_pe[0])
        o_lat = latent_chunk_attention(q, c, layer, tables[0], abs_pos,
                                       mla_softmax_scale(cfg),
                                       cfg.mla.kv_lora_rank)
        out = _mla_out(params, cfg, o_lat, x.dtype)[None]
    return constrain(out, "batch", "seq", "act_embed"), {"c": c}


def decode_scan(step_fn, x: jax.Array, state,
                valid: Optional[jax.Array] = None):
    """Drive a single-token recurrent decode step over K tokens.

    ``step_fn(x_t (B, 1, d), state) -> (out (B, 1, d), new_state)`` is
    any recurrent mixer's decode step (mamba / mLSTM / sLSTM); x is
    (B, K, d).  With ``valid`` (int32 (B,)), rows stop updating their
    state after ``valid[b]`` tokens — the masking that makes K-token
    speculative steps and rollback replays safe for recurrent state
    (tokens past ``valid`` still produce (garbage) outputs but leave
    the carried state untouched).  Returns (out (B, K, d), new_state).
    """
    B, K, _ = x.shape
    if K == 1 and valid is None:
        return step_fn(x, state)
    keep = jnp.ones((K, B), bool) if valid is None else \
        (jnp.arange(K, dtype=jnp.int32)[:, None]
         < jnp.asarray(valid, jnp.int32)[None, :])

    def step(st, inp):
        xt, keep_t = inp                                 # (B, d), (B,)
        out, st_new = step_fn(xt[:, None], st)
        st2 = jax.tree.map(
            lambda n, o: jnp.where(
                keep_t.reshape((B,) + (1,) * (n.ndim - 1)), n, o),
            st_new, st)
        return st2, out[:, 0]

    st, ys = jax.lax.scan(step, state, (x.swapaxes(0, 1), keep))
    return ys.swapaxes(0, 1), st


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int,
                         dtype=None):
    dt = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    axes = {"k": ("batch", "kv_seq", "kv_heads", "head_dim"),
            "v": ("batch", "kv_seq", "kv_heads", "head_dim")}
    return cache, axes


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(keys: KeyGen, cfg: ModelConfig, d_ff: Optional[int] = None
             ) -> Tuple[Params, Params]:
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    f = d_ff or cfg.d_ff
    p = {
        "wi": dense_init(keys(), d, f, dt),
        "wg": dense_init(keys(), d, f, dt),
        "wo": dense_init(keys(), f, d, dt),
    }
    a = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"),
         "wo": ("mlp", "embed")}
    return p, a


def mlp_block(params: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    h = constrain(h, "batch", "seq", "mlp_act")
    return constrain(h @ params["wo"], "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# Mixture of Experts (shared + routed, fine-grained, capacity-based)
# ---------------------------------------------------------------------------


def init_moe(keys: KeyGen, cfg: ModelConfig) -> Tuple[Params, Params]:
    m = cfg.moe
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    d_e = m.d_expert or cfg.d_ff
    E = m.num_experts

    def expert_stack(d_in, d_out):
        ks = keys()
        flat = jax.random.normal(ks, (E, d_in, d_out), jnp.float32)
        return (flat / math.sqrt(d_in)).astype(dt)

    p: Params = {
        "router": dense_init(keys(), d, E, jnp.dtype("float32")),
        "wi": expert_stack(d, d_e),
        "wg": expert_stack(d, d_e),
        "wo": expert_stack(d_e, d),
    }
    a: Params = {
        "router": ("embed", None),
        "wi": ("experts", "embed", "mlp"),
        "wg": ("experts", "embed", "mlp"),
        "wo": ("experts", "mlp", "embed"),
    }
    if m.num_shared_experts:
        sp, sa = init_mlp(keys, cfg, d_ff=d_e * m.num_shared_experts)
        p["shared"] = sp
        a["shared"] = sa
    return p, a


import os as _os
# GShard-style dispatch group size (dispatch tensor volume scales
# linearly with this; perf knob — see EXPERIMENTS.md §Perf)
MOE_GROUP_TOKENS = int(_os.environ.get("REPRO_MOE_GROUP", "1024"))


def moe_block(params: Params, cfg: ModelConfig, x: jax.Array,
              dropless: bool = False, mask: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Capacity-based top-k MoE with GShard group dispatch.

    Tokens are split into groups of ~MOE_GROUP_TOKENS; routing positions
    and the dispatch/combine one-hot tensors are built per group, keeping
    the dispatch cost O(T * k * C_group) instead of O(T^2).  Under EP
    sharding (experts -> "model", groups -> "batch") the (g,e) einsums
    lower to all-to-alls — the MoE communication pattern of the roofline.

    ``dropless=True`` (decode/eval) sizes the buffers so no token is ever
    dropped, making prefill/decode bit-consistent with full forward.
    Returns (out, aux) with load-balancing and z losses.  Given ``mask``
    ((B, S), true on real tokens), aux also counts the real tokens
    routed to each expert (``expert_tokens``, (E,) int32) and the
    routed choices dropped for capacity (``dropped_tokens``).
    """
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.num_experts, m.top_k
    T = B * S
    # pick a group size dividing T
    Tg = min(T, MOE_GROUP_TOKENS)
    while T % Tg:
        Tg -= 1
    G = T // Tg
    xt = x.reshape(G, Tg, d)

    # bf16 inputs, f32 accumulation: avoids materializing + gathering a
    # full f32 copy of the activations just for routing
    logits = jnp.einsum("gtd,de->gte", xt,
                        params["router"].astype(xt.dtype),
                        preferred_element_type=jnp.float32)    # (G,Tg,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (G,Tg,k)
    if m.norm_topk_prob:
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    if dropless:
        capacity = Tg   # per-expert worst case (choices per token distinct)
    else:
        capacity = max(1, int(m.capacity_factor * Tg * k / E))
    # position of each (token, choice) within its expert's buffer (per group)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)      # (G,Tg,k,E)
    flat = onehot.reshape(G, Tg * k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(G, Tg, k, E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)             # (G,Tg,k)
    keep = pos < capacity
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    cdt = jnp.dtype(cfg.dtype)
    if m.dispatch == "scatter":
        # beyond-paper dispatch: scatter tokens straight into the expert
        # buffers and gather them back — O(T*k*d) traffic, zero dispatch
        # matmul flops (vs O(T*E*C) one-hot tensors + 2*T*d*E*C flops)
        gi = jnp.broadcast_to(
            jnp.arange(G, dtype=jnp.int32)[:, None, None], (G, Tg, k))
        pos_c = jnp.where(keep, pos, capacity)         # C = drop slot
        vals = jnp.broadcast_to(xt.astype(cdt)[:, :, None, :],
                                (G, Tg, k, d))
        expert_in = jnp.zeros((G, E, capacity + 1, d), cdt) \
            .at[gi, gate_idx, pos_c].add(vals)[:, :, :capacity]
        expert_in = constrain(expert_in, "batch", "experts_act", None,
                              "act_embed")
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                                   params["wg"])) \
            * jnp.einsum("gecd,edf->gecf", expert_in, params["wi"])
        h = constrain(h, "batch", "experts_act", None, "mlp_act")
        expert_out = jnp.einsum("gecf,efd->gecd", h, params["wo"])
        expert_out = constrain(expert_out, "batch", "experts_act", None,
                               "act_embed")
        pad = jnp.zeros((G, E, 1, d), cdt)
        picked = jnp.concatenate([expert_out, pad], axis=2)[
            gi, gate_idx, pos_c]                        # (G,Tg,k,d)
        out = jnp.sum(picked * gate_vals.astype(cdt)[..., None], axis=2)
    else:
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                                dtype=cdt)              # (G,Tg,k,C)
        disp = jnp.einsum("gtke,gtkc->gtec", onehot.astype(cdt), pos_oh)
        expert_in = jnp.einsum("gtd,gtec->gecd", xt.astype(cdt), disp)
        expert_in = constrain(expert_in, "batch", "experts_act", None,
                              "act_embed")

        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                                   params["wg"])) \
            * jnp.einsum("gecd,edf->gecf", expert_in, params["wi"])
        h = constrain(h, "batch", "experts_act", None, "mlp_act")
        expert_out = jnp.einsum("gecf,efd->gecd", h,
                                params["wo"])           # (G,E,C,d)
        expert_out = constrain(expert_out, "batch", "experts_act", None,
                               "act_embed")

        comb = jnp.einsum("gtke,gtkc,gtk->gtec", onehot.astype(cdt),
                          pos_oh, gate_vals.astype(cdt))
        out = jnp.einsum("gecd,gtec->gtd", expert_out, comb)

    if m.num_shared_experts:
        out = out + mlp_block(params["shared"], xt)

    # aux losses (Switch-style load balance + z-loss)
    density = jnp.mean(
        jnp.max(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=2),
        axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=(0, 1))
    aux = {
        "moe_load_balance": jnp.sum(density * density_proxy) * E,
        "moe_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    if mask is not None:
        real = mask.reshape(G, Tg, 1).astype(jnp.int32)
        aux["expert_tokens"] = jnp.sum(onehot * real[..., None],
                                       axis=(0, 1, 2))
        aux["dropped_tokens"] = jnp.sum((~keep).astype(jnp.int32) * real)
    return out.reshape(B, S, d), aux
