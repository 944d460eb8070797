"""Plain float32 reference of a Qwen3 decoder, and the benchmark's
weights for it.

Follows the published description (hf:Qwen/Qwen3-0.6B): token
embedding; per layer RMSNorm -> attention with grouped KV heads,
RMSNorm of each query and key head, rotary positions on both halves of
the head (``rotate_half``), softmax over the causal context -> residual;
RMSNorm -> SwiGLU MLP (``down(silu(gate(x)) * up(x))``) -> residual;
final RMSNorm and logits against the tied embedding.  Every product runs
in float32 at ``HIGHEST`` precision, over the whole sequence at once,
with no cache and no kernel.  It imports nothing of the program.

:func:`served_gaps` judges greedy tokens against the reference's best,
:func:`sampled_surprise` sampled ones against the reference's
distribution at the request's temperature.
:func:`quantized` makes the control's weights: every weight matrix
rounded to float8 (e4m3, one scale per output column), the step below
bfloat16 that would tempt an optimisation, run through the same
forward.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
MATS = ("wq", "wk", "wv", "wo", "gate", "up", "down")


def shapes(cfg: dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"d": d, "H": H, "Hkv": cfg["num_key_value_heads"],
            "D": cfg.get("head_dim") or d // H,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"])}


@partial(jax.jit, static_argnums=(0,))
def _make(dims, key):
    d, H, Hkv, D, ff, V, L = dims
    ks = jax.random.split(key, 12)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(jnp.bfloat16)

    def scale(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    return {
        "embed": (0.02 * jax.random.normal(ks[0], (V, d), jnp.float32)
                  ).astype(jnp.bfloat16),
        "wq": dense(ks[1], (L, d, H * D), d),
        "wk": dense(ks[2], (L, d, Hkv * D), d),
        "wv": dense(ks[3], (L, d, Hkv * D), d),
        "wo": dense(ks[4], (L, H * D, d), H * D),
        "gate": dense(ks[5], (L, d, ff), d),
        "up": dense(ks[6], (L, d, ff), d),
        "down": dense(ks[7], (L, ff, d), ff),
        "ln1": scale(ks[8], (L, d)),
        "ln2": scale(ks[9], (L, d)),
        "q_norm": scale(ks[10], (L, D)),
        "k_norm": scale(ks[11], (L, D)),
        "final_norm": jnp.ones((d,), jnp.bfloat16),
    }


def make_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    """Random bfloat16 weights from ``key``, made on the device in one
    call: matrices N(0, 1/fan_in), the embedding N(0, 0.02^2), norm
    scales 1 + N(0, 0.01)."""
    s = shapes(cfg)
    return _make((s["d"], s["H"], s["Hkv"], s["D"], s["ff"], s["V"],
                  s["L"]), key)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def _fp8(w):
    """A (.., in, out) matrix as float8 e4m3 with one float32 scale per
    output column."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn), s


@jax.jit
def quantized(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The control's weights: every matrix (the embedding by rows) in
    float8 with its scales.  They are made in a program of their own and
    handed to the forward as float8 arrays: inside one program the
    compiler may drop a float32 -> float8 -> float32 round trip as excess
    precision, and the control would then be the reference itself."""
    out = dict(w)
    for k in MATS:
        out[k] = _fp8(w[k])
    q, s = _fp8(w["embed"].T)
    out["embed"] = (q.T, s.T)
    return out


def _dequant(v):
    if isinstance(v, tuple):
        return v[0].astype(jnp.float32) * v[1]
    return v.astype(jnp.float32)


@partial(jax.jit, static_argnums=(0,))
def _logits(dims, w, tokens, rows):
    """Logits (len(rows), V) at positions ``rows`` of one sequence."""
    H, Hkv, D, eps, theta = dims
    f32 = {k: _dequant(v) for k, v in w.items()}
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = f32["embed"][tokens]
    mask = jnp.tril(jnp.ones((S, S), bool))
    g = H // Hkv

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = jnp.dot(h, p["wq"], precision=HI).reshape(S, H, D)
        k = jnp.dot(h, p["wk"], precision=HI).reshape(S, Hkv, D)
        v = jnp.dot(h, p["wv"], precision=HI).reshape(S, Hkv, D)
        q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(D))
        s = jnp.where(mask[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                       precision=HI).reshape(S, H * D)
        x = x + jnp.dot(a, p["wo"], precision=HI)
        h = _rms(x, p["ln2"], eps)
        m = jax.nn.silu(jnp.dot(h, p["gate"], precision=HI)) \
            * jnp.dot(h, p["up"], precision=HI)
        return x + jnp.dot(m, p["down"], precision=HI), None

    per_layer = {k: f32[k] for k in (*MATS, "ln1", "ln2", "q_norm",
                                     "k_norm")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _rms(x[rows], f32["final_norm"], eps)
    return jnp.dot(x, f32["embed"].T, precision=HI)


def logits(cfg: dict, weights, tokens, rows, bucket: int = 512,
           padded_rows: bool = False):
    """Reference logits at positions ``rows`` of the sequence ``tokens``
    (the logits there predict the token at the next position), with
    ``weights`` as :func:`make_weights` or :func:`quantized` gives them.
    The sequence is right-padded to a multiple of ``bucket`` so that few
    shapes compile; causality keeps the padding out of every row.  With
    ``padded_rows`` the rows stay padded to a multiple of 512 (repeats of
    the last; a request serves at most 512 tokens, so one row count
    compiles), for callers that compile over them."""
    import numpy as np

    s = shapes(cfg)
    n = len(tokens)
    padded = -(-n // bucket) * bucket
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens
    rows = np.asarray(rows, np.int32)
    m = len(rows)
    sel = np.full((-(-m // 512) * 512,), rows[-1], np.int32)
    sel[:m] = rows
    out = _logits((s["H"], s["Hkv"], s["D"], s["eps"], s["theta"]),
                  weights, jnp.asarray(toks), jnp.asarray(sel))
    return out if padded_rows else out[:m]


def served_gaps(cfg: dict, weights, prompt, served, control=None):
    """For each served token: how far its reference logit lies below the
    reference's best at that position.  With ``control`` (the weights
    :func:`quantized` gives) the token judged is the one the float8
    forward puts first."""
    import numpy as np

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
    import numpy as np

    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    z = logits(cfg, weights, seq, np.arange(P - 1, P - 1 + n),
               padded_rows=True)
    toks = np.zeros((z.shape[0],), np.int32)
    toks[:n] = served
    excess, var = _surprise(z, jnp.asarray(toks), jnp.float32(temperature))
    return (np.asarray(excess, np.float64)[:n],
            np.asarray(var, np.float64)[:n])
