"""Plain float32 reference of a DeepSeek-V2 decoder, and the benchmark's
weights for it.

Follows the published description (arXiv:2405.04434 and the model's
``config.json``, hf:deepseek-ai/DeepSeek-V2-Lite): token embedding; per
layer RMSNorm -> multi-head latent attention -> residual; RMSNorm -> FFN
-> residual; final RMSNorm and an untied head.

Attention, in the full (non-absorbed) form: ``q = W_q h`` split per head
into ``q_nope`` and ``q_pe``; ``[c_kv; k_pe] = W_kva h``; ``c_kv`` goes
through its RMSNorm (``kv_a_layernorm``) and ``[k_nope; v] = W_kvb
c_kv`` per head; ``k_pe`` is one rotary head shared by all heads.
Scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(qk_head_dim)`` times
YaRN's ``mscale(factor, mscale_all_dim) ** 2``, causal softmax, values
``v``, then ``o_proj``.  Rotary positions follow DeepSeek's convention:
the rope dims hold interleaved pairs ``(x[2i], x[2i+1])``, turned by
``position * inv_freq[i]``, with YaRN's frequencies (the original ones
above the ``beta_slow`` correction dim, ``1 / factor`` of them below the
``beta_fast`` one, a linear ramp between).

FFN: layers below ``first_k_dense_replace`` are a dense SwiGLU of
``intermediate_size``; every later one an MoE: softmax over the
``n_routed_experts`` router scores, the top ``num_experts_per_tok``
kept as they are (``norm_topk_prob`` false, ``routed_scaling_factor``
1), each a SwiGLU of ``moe_intermediate_size``, plus the shared experts
(one SwiGLU of ``n_shared_experts * moe_intermediate_size``) on every
token.

Every product runs in float32 at ``HIGHEST`` precision over the whole
sequence at once, with no cache and no kernel.  The weights stay in
bfloat16 and are upcast one layer (routed experts one expert) at a
time; attention is taken in blocks of query rows and the routed experts
over the tokens routed to each, so a 35k-token sequence fits beside the
weights.  It imports nothing of the program.

:func:`served_gaps`, :func:`sampled_surprise` and :func:`quantized` play
the parts they play in ``reference/qwen3.py``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ATTN = ("wq", "wkv_a", "wkv_b", "wo")
DENSE = ("gate", "up", "down")
EXPERTS = ("e_gate", "e_up", "e_down")
SHARED = ("s_gate", "s_up", "s_down")
# query rows per attention block
Q_BLOCK = 256


def shapes(cfg: dict) -> Dict[str, object]:
    rs = cfg["rope_scaling"] or {}
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "r_kv": cfg["kv_lora_rank"], "n": cfg["qk_nope_head_dim"],
            "r": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "ff": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "fe": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "k0": cfg["first_k_dense_replace"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "yarn": (float(rs.get("factor", 1.0)),
                     int(rs.get("original_max_position_embeddings", 0)),
                     float(rs.get("beta_fast", 32)),
                     float(rs.get("beta_slow", 1)),
                     float(rs.get("mscale", 1.0)),
                     float(rs.get("mscale_all_dim", 0.0)))}


def _dims(cfg: dict) -> tuple:
    s = shapes(cfg)
    return (s["d"], s["H"], s["r_kv"], s["n"], s["r"], s["v"], s["eps"],
            s["theta"], s["yarn"])


@partial(jax.jit, static_argnums=(0,))
def _make(dims, key):
    d, H, r_kv, n, r, v, ff, E, fe, sh, V, L, k0 = dims
    Lm = L - k0
    ks = iter(jax.random.split(key, 32))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(jnp.bfloat16)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(jnp.bfloat16)

    def attn(m):
        return {"ln1": scale((m, d)), "ln2": scale((m, d)),
                "wq": dense((m, d, H * (n + r)), d),
                "wkv_a": dense((m, d, r_kv + r), d),
                "kv_norm": scale((m, r_kv)),
                "wkv_b": dense((m, r_kv, H * (n + v)), r_kv),
                "wo": dense((m, H * v, d), H * v)}

    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, d), jnp.float32)
                  ).astype(jnp.bfloat16),
        "lm_head": dense((d, V), d),
        "final_norm": jnp.ones((d,), jnp.bfloat16),
        "dense_layers": {**attn(k0), "gate": dense((k0, d, ff), d),
                         "up": dense((k0, d, ff), d),
                         "down": dense((k0, ff, d), ff)},
        "moe_layers": {**attn(Lm), "router": dense((Lm, d, E), d),
                       "e_gate": dense((Lm, E, d, fe), d),
                       "e_up": dense((Lm, E, d, fe), d),
                       "e_down": dense((Lm, E, fe, d), fe),
                       "s_gate": dense((Lm, d, sh * fe), d),
                       "s_up": dense((Lm, d, sh * fe), d),
                       "s_down": dense((Lm, sh * fe, d), sh * fe)},
    }


def make_weights(cfg: dict, key) -> Dict[str, object]:
    """Random bfloat16 weights from ``key``, made on the device in one
    call: matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm
    scales 1 + N(0, 0.01); layers below ``first_k_dense_replace`` under
    ``dense_layers``, the MoE layers under ``moe_layers``."""
    s = shapes(cfg)
    return _make((s["d"], s["H"], s["r_kv"], s["n"], s["r"], s["v"],
                  s["ff"], s["E"], s["fe"], s["shared"], s["V"], s["L"],
                  s["k0"]), key)


# ---- the forward ----------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _inv_freq(r, theta, yarn):
    factor, orig, fast, slow, _, _ = yarn
    base = theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    extra = 1.0 / base
    if factor <= 1:
        return extra.astype(np.float32)
    inter = 1.0 / (factor * base)

    def corr(rot):
        return r * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(fast)), 0)
    hi = min(math.ceil(corr(slow)), r - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(r // 2) - lo) / (hi - lo), 0.0, 1.0)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(x, pos, r, theta, yarn):
    """Interleaved pairs (x[2i], x[2i+1]) turned by pos * inv_freq[i],
    written out de-interleaved as [evens; odds] (the order DeepSeek's
    code leaves them in; q and k share it, so their products do not
    depend on it).  x: (S, ..., r)."""
    factor, _, _, _, ms, ms_all = yarn
    m = _mscale(factor, ms) / _mscale(factor, ms_all) if ms_all else \
        _mscale(factor, ms)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        _inv_freq(r, theta, yarn))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,)
    cos = (jnp.cos(ang) * m).reshape(shape)
    sin = (jnp.sin(ang) * m).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dequant(v):
    if isinstance(v, tuple):
        return v[0].astype(jnp.float32) * v[1]
    return v.astype(jnp.float32)


@partial(jax.jit, static_argnums=(0,))
def _attention(dims, p, x, n_real):
    """x + MLA(RMSNorm(x)) over the whole (padded) sequence; the first
    ``n_real`` positions are the sequence, the padding is causally
    invisible to them."""
    d, H, r_kv, n, r, v, eps, theta, yarn = dims
    p = {k: _dequant(val) for k, val in p.items()}
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"], eps)
    q = jnp.dot(h, p["wq"], precision=HI).reshape(S, H, n + r)
    q_nope, q_pe = q[..., :n], _rope(q[..., n:], pos, r, theta, yarn)
    kv_a = jnp.dot(h, p["wkv_a"], precision=HI)
    c = _rms(kv_a[:, :r_kv], p["kv_norm"], eps)
    k_pe = _rope(kv_a[:, r_kv:], pos, r, theta, yarn)
    kv = jnp.dot(c, p["wkv_b"], precision=HI).reshape(S, H, n + v)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_pe[:, None], (S, H, r))], -1)
    vals = kv[..., n:]
    q = jnp.concatenate([q_nope, q_pe], -1)
    factor, _, _, _, _, ms_all = yarn
    sm = (n + r) ** -0.5 * (_mscale(factor, ms_all) ** 2 if ms_all else 1.0)

    blk = math.gcd(S, Q_BLOCK)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * sm
        rows = i * blk + jnp.arange(blk)
        s = jnp.where((pos[None, :] <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1), vals,
                          precision=HI)

    a = jax.lax.map(block, jnp.arange(S // blk)).reshape(S, H * v)
    return x + jnp.dot(a, p["wo"], precision=HI)


def _swiglu(h, g, u, dn):
    return jnp.dot(jax.nn.silu(jnp.dot(h, g, precision=HI))
                   * jnp.dot(h, u, precision=HI), dn, precision=HI)


@partial(jax.jit, static_argnums=(0,))
def _dense_ffn(eps, p, x):
    p = {k: _dequant(val) for k, val in p.items()}
    h = _rms(x, p["ln2"], eps)
    return x + _swiglu(h, p["gate"], p["up"], p["down"])


@partial(jax.jit, static_argnums=(0,))
def _route(dims, p, x):
    """The normalised hidden state, the router's softmax and its top-k
    (experts, scores)."""
    eps, k, norm_topk, route_scale = dims
    h = _rms(x, _dequant(p["ln2"]), eps)
    probs = jax.nn.softmax(
        jnp.dot(h, _dequant(p["router"]), precision=HI), -1)
    g, e = jax.lax.top_k(probs, k)
    if norm_topk:
        g = g / jnp.sum(g, -1, keepdims=True)
    return h, e, g * route_scale


@jax.jit
def _experts(p, h, x, idx, gates):
    """x + shared experts(h) + sum over experts e of gates[e] *
    expert_e(h[idx[e]]), one expert's weights upcast at a time; idx pads
    with row S (a zero row), gates with 0."""
    S = h.shape[0]
    out = x + _swiglu(h, *(_dequant(p[k]) for k in SHARED))
    hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])

    def one(acc, e):
        w = [_dequant(jax.tree.map(lambda a: a[e], p[k])) for k in EXPERTS]
        y = _swiglu(hp[idx[e]], *w) * gates[e][:, None]
        return acc.at[idx[e]].add(y, mode="drop"), None

    acc, _ = jax.lax.scan(one, jnp.zeros((S + 1, h.shape[1]), jnp.float32),
                          jnp.arange(idx.shape[0]))
    return out + acc[:S]


def _moe_ffn(cfg: dict, p, x, n_real: int):
    s = shapes(cfg)
    h, e, g = _route((s["eps"], s["k"], s["norm_topk"], s["route_scale"]),
                     p, x)
    e, g = np.asarray(e[:n_real]), np.asarray(g[:n_real])
    E, S = s["E"], x.shape[0]
    rows = [np.nonzero(e == i) for i in range(E)]
    cap = 1 << max(0, int(max(len(t) for t, _ in rows) - 1).bit_length())
    idx = np.full((E, cap), S, np.int32)
    gates = np.zeros((E, cap), np.float32)
    for i, (t, j) in enumerate(rows):
        idx[i, :len(t)] = t
        gates[i, :len(t)] = g[t, j]
    return _experts(p, h, x, jnp.asarray(idx), jnp.asarray(gates))


def _layer(stack, i):
    return jax.tree.map(lambda a: a[i], stack,
                        is_leaf=lambda a: isinstance(a, tuple))


@jax.jit
def _fp8(w):
    """A (.., in, out) matrix as float8 e4m3 with one float32 scale per
    output column."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    sc = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / sc).astype(jnp.float8_e4m3fn), sc


def _quantize_layer(p: dict) -> dict:
    """One layer's matrices in float8, each made in a program of its own
    and handed to the forward as float8 arrays (inside one program the
    compiler may drop a float32 -> float8 -> float32 round trip)."""
    out = dict(p)
    for k in ATTN + DENSE + SHARED + ("router",):
        if k in p:
            out[k] = _fp8(p[k])
    for k in EXPERTS:
        if k in p:
            out[k] = _fp8(p[k])
    return out


def quantized(w: dict) -> dict:
    """The control's weights: every matrix of every layer (and the head
    and embedding) in float8 e4m3 with a float32 scale per output
    column, the step below bfloat16; layers are quantized as the
    forward reaches them, so only one layer's float8 copy is held."""
    q, sc = _fp8(w["embed"].T)
    return {**w, "control": True, "embed": (q.T, sc.T),
            "lm_head": _fp8(w["lm_head"])}


@partial(jax.jit, static_argnums=(0,))
def _head(eps, final_norm, lm_head, x, rows):
    h = _rms(x[rows], final_norm, eps)
    return jnp.dot(h, _dequant(lm_head), precision=HI)


def logits(cfg: dict, weights, tokens, rows, bucket: int = 1024):
    """Reference logits (len(rows), V) at positions ``rows`` of the
    sequence ``tokens`` (the logits there predict the next token), with
    ``weights`` as :func:`make_weights` or :func:`quantized` gives
    them.  The sequence is right-padded to a multiple of ``bucket`` so
    that few shapes compile; causality keeps the padding out of every
    row."""
    s = shapes(cfg)
    dims = _dims(cfg)
    control = bool(weights.get("control"))
    n = len(tokens)
    padded = -(-n // bucket) * bucket
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens
    x = jnp.take(_dequant(weights["embed"]), jnp.asarray(toks), axis=0)
    for stack, moe in ((weights["dense_layers"], False),
                       (weights["moe_layers"], True)):
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            p = _layer(stack, i)
            if control:
                p = _quantize_layer(p)
            x = _attention(dims, {k: p[k] for k in
                                  ATTN + ("ln1", "kv_norm")}, x, n)
            if moe:
                x = _moe_ffn(cfg, {k: p[k] for k in
                                   EXPERTS + SHARED + ("router", "ln2")},
                             x, n)
            else:
                x = _dense_ffn(s["eps"], {k: p[k] for k in
                                          DENSE + ("ln2",)}, x)
    rows = np.asarray(rows, np.int32)
    m = len(rows)
    sel = np.full((-(-m // 512) * 512,), rows[-1], np.int32)
    sel[:m] = rows
    out = _head(s["eps"], weights["final_norm"], weights["lm_head"], x,
                jnp.asarray(sel))
    return out[:m]


def served_gaps(cfg: dict, weights, prompt, served, control=None):
    """For each served token: how far its reference logit lies below the
    reference's best at that position.  With ``control`` (the weights
    :func:`quantized` gives) the token judged is the one the float8
    forward puts first."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    rows = np.arange(P - 1, P - 1 + n)
    ref = np.asarray(logits(cfg, weights, seq, rows))
    if control is not None:
        chosen = np.asarray(logits(cfg, control, seq, rows)).argmax(-1)
    else:
        chosen = np.asarray(served)
    return ref.max(-1) - ref[np.arange(n), chosen]


@jax.jit
def _surprise(z, served, temperature):
    lp = jax.nn.log_softmax(z / temperature, -1)
    p = jnp.exp(lp)
    entropy = -jnp.sum(p * lp, -1)
    nll = -jnp.take_along_axis(lp, served[:, None], -1)[:, 0]
    return nll - entropy, jnp.sum(p * lp * lp, -1) - entropy * entropy


def sampled_surprise(cfg: dict, weights, prompt, served, temperature: float):
    """For each served token of a request sampled at ``temperature``: its
    negative log-likelihood under the reference's distribution at that
    temperature less that distribution's entropy (zero in expectation
    when the token was drawn from it), and that difference's variance."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    z = logits(cfg, weights, seq, np.arange(P - 1, P - 1 + n))
    excess, var = _surprise(z, jnp.asarray(np.asarray(served, np.int32)),
                            jnp.float32(temperature))
    return (np.asarray(excess, np.float64), np.asarray(var, np.float64))
