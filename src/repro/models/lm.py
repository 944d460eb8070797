"""Decoder-LM composition: embeds -> scanned block stack -> logits.

Handles every assigned architecture family through `ModelConfig`:
  dense / moe   — attention + (SwiGLU | MoE) blocks
  hybrid        — jamba-style mamba/attention interleave (+ periodic MoE)
  ssm           — xLSTM mLSTM/sLSTM stacks (no FFN)
  vlm / audio   — same backbone; modality frontends are stubs that feed
                  precomputed embeddings (`batch["embeds"]`) / token ids.

The layer stack is grouped into a repeating *period* (lcm of the block
pattern and the MoE period); parameters are stacked over periods and the
stack is driven by ``jax.lax.scan`` — this keeps HLO size and compile time
independent of depth, and gives the FSDP all-gather/compute overlap
pattern on the period boundary.

Three entry points:
  ``lm_loss``      — next-token CE + MoE aux losses (training)
  ``lm_prefill``   — forward + cache construction (full prompt, or one
                   chunked slice over a paged pool when ``tables`` is
                   given)
  ``lm_decode``    — K >= 1 tokens per row over the paged pool,
                   addressed through block tables; K > 1 is the
                   speculative-verify step

The serving layer drives these exclusively through
:class:`repro.serve.session.DecodeSession`, which pairs them with a
:class:`repro.serve.kv_cache.PagedLayout` and owns the jit boundaries.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.parallel.sharding import constrain

Params = Dict[str, Any]


class LayerSpec(NamedTuple):
    kind: str      # 'a' attention | 'l' latent attention (MLA)
    #                | 'M' mamba | 'm' mLSTM | 's' sLSTM
    ffn: str       # 'dense' | 'moe' | 'none'
    d_ff: int


# mixers whose cache is pages of a shared pool (prompts may be padded and
# prefilled in chunks)
ATTENTION_KINDS = ("a", "l")


def layer_specs(cfg: ModelConfig) -> Tuple[LayerSpec, ...]:
    specs = []
    if cfg.family == "ssm" and cfg.xlstm is not None:
        pat = cfg.xlstm.pattern
        return tuple(LayerSpec(pat[i % len(pat)], "none", 0)
                     for i in range(cfg.num_layers))
    pat = cfg.block_pattern
    for i in range(cfg.num_layers):
        kind = pat[i % len(pat)]
        if kind == "a" and cfg.mla is not None:
            kind = "l"
        if cfg.moe is not None and cfg.is_moe_layer(i):
            ffn = "moe"
            d_ff = 0
        elif cfg.moe is not None and i < cfg.moe.first_k_dense:
            ffn, d_ff = "dense", (cfg.moe.dense_d_ff or cfg.d_ff)
        elif cfg.d_ff > 0:
            ffn, d_ff = "dense", cfg.d_ff
        else:
            ffn, d_ff = "none", 0
        specs.append(LayerSpec(kind, ffn, d_ff))
    return tuple(specs)


def _grouping(cfg: ModelConfig) -> Tuple[int, int, int]:
    """Return (k0 prefix layers, period length R, num periods P)."""
    specs = layer_specs(cfg)
    k0 = cfg.moe.first_k_dense if cfg.moe is not None else 0
    body = len(specs) - k0
    pat_len = len(cfg.xlstm.pattern) if (cfg.family == "ssm" and cfg.xlstm) \
        else len(cfg.block_pattern)
    moe_p = cfg.moe.moe_period if (cfg.moe and cfg.moe.moe_period > 1) else 1
    R = math.lcm(pat_len, moe_p)
    assert body % R == 0, (cfg.name, body, R)
    # periods must be homogeneous
    for j in range(R):
        kinds = {specs[k0 + p * R + j] for p in range(body // R)}
        assert len(kinds) == 1, (cfg.name, j, kinds)
    return k0, R, body // R


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(keys: L.KeyGen, cfg: ModelConfig, spec: LayerSpec):
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    p: Params = {}
    a: Params = {}
    p["ln1"], a["ln1"] = L.init_rmsnorm(d, dt)
    if spec.kind == "a":
        p["mixer"], a["mixer"] = L.init_attention(keys, cfg)
    elif spec.kind == "l":
        p["mixer"], a["mixer"] = L.init_mla(keys, cfg)
    elif spec.kind == "M":
        p["mixer"], a["mixer"] = S.init_mamba(keys, cfg)
    elif spec.kind == "m":
        p["mixer"], a["mixer"] = X.init_mlstm(keys, cfg)
    elif spec.kind == "s":
        p["mixer"], a["mixer"] = X.init_slstm(keys, cfg)
    else:
        raise ValueError(spec.kind)
    if spec.ffn == "dense":
        p["ln2"], a["ln2"] = L.init_rmsnorm(d, dt)
        p["ffn"], a["ffn"] = L.init_mlp(keys, cfg, d_ff=spec.d_ff)
    elif spec.ffn == "moe":
        p["ln2"], a["ln2"] = L.init_rmsnorm(d, dt)
        p["ffn"], a["ffn"] = L.init_moe(keys, cfg)
    return p, a


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _push_axis(axes_tree, name):
    return jax.tree.map(
        lambda t: (name,) + t,
        axes_tree,
        is_leaf=lambda t: isinstance(t, tuple)
        and all(x is None or isinstance(x, str) for x in t))


def init_lm(cfg: ModelConfig, key: jax.Array) -> Tuple[Params, Params]:
    keys = L.KeyGen(key)
    dt = jnp.dtype(cfg.dtype)
    specs = layer_specs(cfg)
    k0, R, P = _grouping(cfg)

    p: Params = {"embed": L.embed_init(keys(), cfg.vocab_size, cfg.d_model, dt)}
    a: Params = {"embed": ("vocab", "embed")}

    if k0:
        pref = [_init_block(keys, cfg, specs[i]) for i in range(k0)]
        p["prefix"] = _stack([x[0] for x in pref])
        a["prefix"] = _push_axis(pref[0][1], "layers")

    body_p, body_a = [], []
    for j in range(R):
        per = [_init_block(keys, cfg, specs[k0 + pi * R + j])
               for pi in range(P)]
        body_p.append(_stack([x[0] for x in per]))
        body_a.append(_push_axis(per[0][1], "period"))
    p["body"] = tuple(body_p)
    a["body"] = tuple(body_a)

    p["final_norm"], a["final_norm"] = L.init_rmsnorm(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(keys(), cfg.d_model, cfg.vocab_size, dt)
        a["lm_head"] = ("embed", "vocab")
    return p, a


# ---------------------------------------------------------------------------
# block apply (train / prefill / decode)
# ---------------------------------------------------------------------------


def _zero_aux():
    return {"moe_load_balance": jnp.zeros((), jnp.float32),
            "moe_z": jnp.zeros((), jnp.float32)}


def _apply_block(bp: Params, cfg: ModelConfig, spec: LayerSpec, x, positions,
                 mode: str, cache=None, index=None, tables=None,
                 hist_len=None, prompt_len=None, valid=None, layer=None,
                 token_mask=None):
    """Returns (x, new_cache, aux).

    Modes "decode" and "chunk" run on the paged pool through
    ``tables``: "decode" uses the gather-decode kernel over scattered
    pages and "chunk" runs one chunked-prefill slice (attention-only
    stacks).  Recurrent mixers keep their per-slot state rows in both.
    Decode mode handles K >= 1 tokens per row; ``valid`` (int32 (B,))
    marks how many of the K are real per row — attention routes the
    rest to the null page and recurrent mixers freeze their state past
    it (the speculative verify / rollback-replay contract).  Attention
    ('a') and latent attention ('l') get the WHOLE stacked pool as
    ``cache`` and their own index in the stack as ``layer``, and return
    the whole pool.
    ``token_mask`` ((B, S), true on real tokens) makes
    an MoE layer count its routing into ``aux``.
    """
    if mode == "chunk" and spec.kind not in ATTENTION_KINDS:
        raise ValueError(
            "chunked prefill requires an attention-only stack "
            f"(got mixer kind {spec.kind!r})")
    aux = _zero_aux()
    h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
    multi = mode == "decode" and (h.shape[1] > 1 or valid is not None)
    new_cache = None
    if spec.kind == "a":
        if mode == "train":
            mix = L.attention_block(bp["mixer"], cfg, h, positions)
        elif mode == "prefill":
            mix, new_cache = L.attention_prefill(bp["mixer"], cfg, h,
                                                 positions)
        elif mode == "chunk":
            mix, new_cache = L.attention_chunk_paged(
                bp["mixer"], cfg, h, cache, layer, tables, hist_len,
                prompt_len, positions)
        else:
            mix, new_cache = L.attention_decode_paged(
                bp["mixer"], cfg, h, cache, layer, index, positions, tables,
                valid=valid)
    elif spec.kind == "l":
        if mode == "train":
            mix = L.mla_block(bp["mixer"], cfg, h, positions)
        elif mode == "prefill":
            mix, new_cache = L.mla_block(bp["mixer"], cfg, h, positions,
                                         return_cache=True)
        elif mode == "chunk":
            mix, new_cache = L.mla_chunk_paged(
                bp["mixer"], cfg, h, cache, layer, tables, hist_len,
                prompt_len, positions)
        else:
            mix, new_cache = L.mla_decode_paged(
                bp["mixer"], cfg, h, cache, layer, index, positions, tables,
                valid=valid)
    elif spec.kind == "M":
        if mode == "train":
            mix = S.mamba_block(bp["mixer"], cfg, h)
        elif mode == "prefill":
            mix, new_cache = S.mamba_prefill(bp["mixer"], cfg, h)
        elif multi:
            mix, new_cache = S.mamba_decode_multi(bp["mixer"], cfg, h,
                                                  cache, valid)
        else:
            mix, new_cache = S.mamba_decode(bp["mixer"], cfg, h, cache)
    elif spec.kind == "m":
        if multi:
            mix, new_cache = X.mlstm_decode_multi(bp["mixer"], cfg, h,
                                                  cache, valid)
        elif mode == "decode":
            mix, new_cache = X.mlstm_decode(bp["mixer"], cfg, h, cache)
        else:
            mix, new_cache = X.mlstm_block(bp["mixer"], cfg, h,
                                           return_state=True)
    elif spec.kind == "s":
        if multi:
            mix, new_cache = X.slstm_decode_multi(bp["mixer"], cfg, h,
                                                  cache, valid)
        elif mode == "decode":
            mix, new_cache = X.slstm_decode(bp["mixer"], cfg, h, cache)
        else:
            mix, new_cache = X.slstm_block(bp["mixer"], cfg, h,
                                           return_state=True)
    else:
        raise ValueError(spec.kind)
    x = x + mix
    x = constrain(x, "batch", "seq_sp", "act_embed")
    if spec.ffn != "none":
        h2 = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
        if spec.ffn == "moe":
            with jax.named_scope("moe"):
                f, moe_aux = L.moe_block(bp["ffn"], cfg, h2,
                                         dropless=(mode != "train"),
                                         mask=token_mask)
            aux = {**moe_aux, **{k: aux[k] + moe_aux[k] for k in aux}}
        else:
            f = L.mlp_block(bp["ffn"], h2)
        x = x + f
        x = constrain(x, "batch", "seq_sp", "act_embed")
    return x, new_cache, aux


def _period_specs(cfg: ModelConfig) -> Tuple[LayerSpec, ...]:
    specs = layer_specs(cfg)
    k0, R, _ = _grouping(cfg)
    return tuple(specs[k0:k0 + R])


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if remat == "dots_no_batch":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # 'full'


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _embed_in(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    if "embeds" in batch:                 # vlm stub frontend
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    return constrain(x, "batch", "seq_sp", "act_embed")


def _positions_of(batch, cfg: ModelConfig, B, S, index=None):
    if "positions" in batch:
        return batch["positions"]
    if index is None:
        return L.default_positions(B, S)
    return jnp.full((B, 1), index, jnp.int32)


def _logits(params, cfg: ModelConfig, x):
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return constrain(logits, "batch", "seq", "vocab")


def lm_forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
               remat: str = "none"):
    """Full forward (training). Returns (logits, aux)."""
    x = _embed_in(params, cfg, batch)
    B, S, _ = x.shape
    positions = _positions_of(batch, cfg, B, S)
    pspecs = _period_specs(cfg)
    specs = layer_specs(cfg)

    def run_stack(x, stacked, spec_list):
        # remat granularity is ONE BLOCK (not the whole period): the
        # backward pass then holds a single block's recomputed
        # activations at a time — this is what keeps the 72-layer 398B
        # hybrid period under HBM.
        def apply_one(sp):
            def f(lp, xc):
                return _apply_block(lp, cfg, sp, xc, positions, "train")
            return _remat_wrap(f, remat)

        fns = [apply_one(sp) for sp in spec_list]

        def body(xc, layer_p):
            aux_tot = _zero_aux()
            if not isinstance(layer_p, tuple):
                layer_p = (layer_p,)
            for fn, lp in zip(fns, layer_p):
                xc, _, aux = fn(lp, xc)
                aux_tot = {k: aux_tot[k] + aux[k] for k in aux_tot}
            return xc, aux_tot

        x, auxs = jax.lax.scan(body, x, stacked)
        return x, jax.tree.map(jnp.sum, auxs)

    aux = _zero_aux()
    if "prefix" in params:
        # prefix layers are homogeneous by construction (first_k_dense)
        x, a1 = run_stack(x, params["prefix"], (specs[0],))
        aux = {k: aux[k] + a1[k] for k in aux}
    x, a2 = run_stack(x, tuple(params["body"]), pspecs)
    aux = {k: aux[k] + a2[k] for k in aux}
    return _logits(params, cfg, x), aux


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: str = "none"):
    """Next-token cross entropy + MoE aux. Returns (loss, metrics)."""
    logits, aux = lm_forward(params, cfg, batch, remat)
    labels = batch["labels"]
    lg = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux["moe_load_balance"] \
            + cfg.moe.router_z_weight * aux["moe_z"]
    metrics = {"ce": ce, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# cache init / prefill / decode
# ---------------------------------------------------------------------------


def _init_state_rows(cfg: ModelConfig, spec: LayerSpec, batch: int):
    if spec.kind == "M":
        return S.init_mamba_state(cfg, batch)
    if spec.kind == "m":
        return X.init_mlstm_state(cfg, batch)
    if spec.kind == "s":
        return X.init_slstm_state(cfg, batch)
    raise ValueError(spec.kind)


def init_cache(cfg: ModelConfig, batch: int,
               pages: Optional[Tuple[int, int]] = None):
    """Cache pytree mirroring the stacked param structure.

    ``pages=(num_pages, block_size)`` gives attention layers ONE shared
    ``(num_pages + 1, block_size, Hkv, D)`` pool each (page
    ``num_pages`` is the null page), addressed through per-request
    block tables, so a request's KV can be scattered anywhere in the
    pool; a stack's pools are allocated already stacked
    (:func:`repro.models.layers.init_paged_attention_pool`), as the
    layer scan carries them.  Latent-attention layers get one latent
    pool per stack, also allocated stacked
    (:func:`repro.models.layers.init_latent_pool`).  A stack with
    attention or latent layers needs ``pages``.  Recurrent layers
    (mamba / xLSTM) carry O(1) state per request and keep ``batch``
    rows.  An MoE model with latent layers also gets ``moe_stats``:
    the real tokens each layer routed to each expert
    (``expert_tokens``, (num_layers, E) int32) and the routed choices
    dropped for capacity (``dropped_tokens``), counted on the device by
    every decode step and prefill chunk.  Returns (cache, axes); axes
    leaves containing ``"pages"`` identify the pools, ``"counters"``
    the counters, everything else is the recurrent state that
    snapshot/restore (speculative rollback) copies.
    """
    specs = layer_specs(cfg)
    k0, R, P = _grouping(cfg)
    cache: Params = {}
    axes: Params = {}

    def stack(spec, n, axis):
        # ``n`` layers of one (homogeneous) stack, stacked on ``axis``
        if spec.kind in ATTENTION_KINDS and pages is None:
            raise ValueError("attention keeps its cache in a paged pool "
                             "only (pass pages=)")
        if spec.kind == "l":
            return L.init_latent_pool(cfg, n, pages[0], pages[1])
        if spec.kind == "a":
            c, a = L.init_paged_attention_pool(cfg, n, pages[0], pages[1])
            return c, _push_axis(a, axis)
        per = [_init_state_rows(cfg, spec, batch) for _ in range(n)]
        return _stack([c for c, _ in per]), _push_axis(per[0][1], axis)

    if k0:
        cache["prefix"], axes["prefix"] = stack(specs[0], k0, "layers")
    body = [stack(specs[k0 + j], P, "period") for j in range(R)]
    cache["body"] = tuple(c for c, _ in body)
    axes["body"] = tuple(a for _, a in body)
    if cfg.moe is not None and any(s.kind == "l" for s in specs):
        E = cfg.moe.num_experts
        cache["moe_stats"] = {
            "expert_tokens": jnp.zeros((cfg.num_layers, E), jnp.int32),
            "dropped_tokens": jnp.zeros((cfg.num_layers,), jnp.int32)}
        axes["moe_stats"] = {"expert_tokens": ("counters", None),
                             "dropped_tokens": ("counters",)}
    return cache, axes


def _run_carried(cfg: ModelConfig, x, params: Params, cache: Params,
                 apply):
    """Drive a stack on the paged pool: ``apply(spec, layer_params,
    cache, layer, x) -> (x, cache, aux)`` per layer, the prefix and body
    caches in the scan carry and the layer's index in the stack as the
    scanned input.  Page pools (K/V or latent) stay whole: each layer
    writes and reads its own slice in place, where scanning over them
    would copy every layer's pool out and back each call.  A recurrent
    layer's state rows (small) are sliced out for ``apply`` and written
    back.  Adds each layer's routing counts into ``cache["moe_stats"]``
    when it is there.  Returns (x, new_cache)."""
    specs = layer_specs(cfg)
    k0, R, _ = _grouping(cfg)
    counting = "moe_stats" in cache
    E = cfg.moe.num_experts if counting else 0

    def run(x, stacked, pools, spec_list):
        single = not isinstance(stacked, tuple)
        n = jax.tree.leaves(stacked)[0].shape[0]

        def body(carry, inp):
            xc, cs = carry
            lps, li = inp
            lps = (lps,) if single else lps
            cs = (cs,) if single else cs
            new, tok, drop = [], [], []
            for sp, lp, lc in zip(spec_list, lps, cs):
                if sp.kind in ATTENTION_KINDS:
                    xc, nc, aux = apply(sp, lp, lc, li, xc)
                else:
                    st = jax.tree.map(
                        lambda s: jax.lax.dynamic_index_in_dim(
                            s, li, keepdims=False), lc)
                    xc, ns, aux = apply(sp, lp, st, li, xc)
                    nc = jax.tree.map(
                        lambda s, n: jax.lax.dynamic_update_index_in_dim(
                            s, n, li, 0), lc, ns)
                new.append(nc)
                tok.append(aux.get("expert_tokens",
                                   jnp.zeros((E,), jnp.int32)))
                drop.append(aux.get("dropped_tokens",
                                    jnp.zeros((), jnp.int32)))
            cs = new[0] if single else tuple(new)
            ys = (jnp.stack(tok), jnp.stack(drop)) if counting else None
            return (xc, cs), ys

        (x, pools), ys = jax.lax.scan(
            body, (x, pools), (stacked, jnp.arange(n, dtype=jnp.int32)))
        if counting:
            ys = (ys[0].reshape(-1, E), ys[1].reshape(-1))
        return x, pools, ys

    new_cache: Params = {}
    counts = []
    if "prefix" in params:
        x, new_cache["prefix"], ys = run(x, params["prefix"],
                                         cache["prefix"], (specs[0],))
        counts.append(ys)
    x, new_cache["body"], ys = run(x, tuple(params["body"]),
                                   tuple(cache["body"]),
                                   tuple(specs[k0:k0 + R]))
    counts.append(ys)
    if counting:
        st = cache["moe_stats"]
        new_cache["moe_stats"] = {
            "expert_tokens": st["expert_tokens"]
            + jnp.concatenate([c[0] for c in counts]),
            "dropped_tokens": st["dropped_tokens"]
            + jnp.concatenate([c[1] for c in counts])}
    return x, new_cache


def _prefill_chunk(params: Params, cfg: ModelConfig, tokens: jax.Array,
                   cache: Params, tables: jax.Array, hist_len: jax.Array,
                   prompt_len: jax.Array, last_pos: jax.Array):
    """One chunked-prefill slice for a single request (paged pool).

    tokens: (1, C) — prompt positions [hist_len, hist_len + C), tail
    chunk right-padded past ``prompt_len``; tables: (1, W) the
    request's block-table row; hist_len / prompt_len: int32 scalars;
    last_pos: int32 (1,) position WITHIN the chunk to read logits from
    (only meaningful on the final chunk).  Attention-only stacks —
    recurrent mixers cannot resume mid-prompt from a page pool (see
    ROADMAP "recurrent-family prompt bucketing").  Returns
    (logits (1, 1, V), new_cache).
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, "batch", "seq", "act_embed")
    B, C, _ = x.shape
    hist_len = jnp.asarray(hist_len, jnp.int32)
    pos = hist_len + jnp.arange(C, dtype=jnp.int32)[None, :]
    if cfg.use_mrope:
        positions = jnp.broadcast_to(pos[None], (3, B, C))
    else:
        positions = pos
    real = pos < prompt_len

    def apply(sp, lp, lc, li, xc):
        return _apply_block(lp, cfg, sp, xc, positions, "chunk",
                            cache=lc, tables=tables, hist_len=hist_len,
                            prompt_len=prompt_len, layer=li,
                            token_mask=real)

    x, new_cache = _run_carried(cfg, x, params, cache, apply)
    idx = jnp.broadcast_to(
        jnp.asarray(last_pos, jnp.int32)[:, None, None],
        (x.shape[0], 1, x.shape[2]))
    sel = jnp.take_along_axis(x, idx, axis=1)
    logits = _logits(params, cfg, sel)
    return logits, new_cache


def lm_prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
               last_pos: Optional[jax.Array] = None,
               cache: Optional[Params] = None,
               tables: Optional[jax.Array] = None,
               hist_len: Optional[jax.Array] = None,
               prompt_len: Optional[jax.Array] = None):
    """Process prompt tokens; returns (selected logits, cache).

    Two modes behind one entry point:

    * **full prefill** (``tables=None``, the default): forward the
      whole prompt and build a fresh per-layer cache (K/V or latent
      rows per position, recurrent state), which
      :meth:`repro.serve.kv_cache.PagedLayout.insert_prefill` scatters
      into the pool.  ``last_pos`` (int32
      (B,), optional) selects the hidden state each row's logits are
      read from instead of position ``S - 1`` — the serving scheduler
      right-pads prompts to a shape bucket and reads logits at each
      request's true last token.
    * **chunked prefill** (``tables`` given): one slice of a single
      request scattered straight into the shared paged pool passed as
      ``cache`` — see :func:`_prefill_chunk` for the slice contract.
    """
    if tables is not None:
        return _prefill_chunk(params, cfg, batch["tokens"], cache, tables,
                              hist_len, prompt_len, last_pos)
    x = _embed_in(params, cfg, batch)
    B, S, _ = x.shape
    positions = _positions_of(batch, cfg, B, S)
    pspecs = _period_specs(cfg)
    specs = layer_specs(cfg)

    def run_stack(x, stacked, spec_list):
        def body(xc, layer_p):
            if not isinstance(layer_p, tuple):
                layer_p = (layer_p,)
            caches = []
            for sp, lp in zip(spec_list, layer_p):
                xc, c, _ = _apply_block(lp, cfg, sp, xc, positions, "prefill")
                caches.append(c)
            return xc, tuple(caches)

        return jax.lax.scan(body, x, stacked)

    caches: Params = {}
    if "prefix" in params:
        x, pc = run_stack(x, params["prefix"], (specs[0],))
        caches["prefix"] = pc[0]
    x, bc = run_stack(x, tuple(params["body"]), pspecs)
    caches["body"] = bc
    if last_pos is None:
        sel = x[:, -1:]
    else:
        idx = jnp.broadcast_to(
            jnp.asarray(last_pos, jnp.int32)[:, None, None],
            (x.shape[0], 1, x.shape[2]))
        sel = jnp.take_along_axis(x, idx, axis=1)
    logits = _logits(params, cfg, sel)
    return logits, caches


def lm_decode(params: Params, cfg: ModelConfig, tokens: jax.Array,
              cache: Params, index: jax.Array, *, tables: jax.Array,
              valid: Optional[jax.Array] = None):
    """One decode step over K >= 1 tokens per row, on the paged pool.

    tokens: (B, K) int32 — K = 1 is the classic single-token decode;
    K > 1 is the speculative-verify step (K consecutive tokens per row,
    logits returned for every position).  index: int32 (B,) per-row
    write position of each row's first token (= its current KV length;
    continuous batching: each batch row is a different request at a
    different length), or a scalar for every row; token t of row b
    lands at ``index[b] + t``, and an index of -1 marks an idle row
    (writes route to the null page).  tables: (B, W) int32 block
    tables into the pool.  ``valid`` (int32 (B,), optional) caps the
    real tokens per row: beyond it attention writes route to the null
    page and recurrent state freezes — the primitive speculative
    decoding's rollback replay is built on.  The stacked pool rides in
    the layer scan's carry (:func:`_run_carried`).  Returns (logits
    (B, K, V), new_cache)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, "batch", "seq", "act_embed")
    B, K = tokens.shape
    index = jnp.asarray(index, jnp.int32)
    if valid is not None:
        valid = jnp.asarray(valid, jnp.int32)
    idx_col = index[:, None] if index.ndim else \
        jnp.full((B, 1), index, jnp.int32)
    idx_col = jnp.maximum(idx_col, 0)           # the idle-row sentinel
    pos = idx_col + jnp.arange(K, dtype=jnp.int32)[None, :]
    if cfg.use_mrope:
        # text decode: all three M-RoPE components advance together
        positions = jnp.broadcast_to(pos[None], (3, B, K))
    else:
        positions = pos

    real = jnp.broadcast_to(index >= 0, (B,))[:, None] \
        & jnp.ones((1, K), bool)
    if valid is not None:
        real = real & (jnp.arange(K, dtype=jnp.int32)[None, :]
                       < valid[:, None])

    def apply(sp, lp, lc, li, xc):
        return _apply_block(lp, cfg, sp, xc, positions, "decode",
                            cache=lc, index=index, tables=tables,
                            valid=valid, layer=li, token_mask=real)

    x, new_cache = _run_carried(cfg, x, params, cache, apply)
    return _logits(params, cfg, x), new_cache
