"""DecodeSession: ONE decode surface for every cache layout and family.

Before this module the serving stack picked model entry points by hand
— a dense decode vs a paged one, a full prefill vs a chunked slice —
once per cache layout, and every caller (scheduler, engine, launcher,
examples, benchmarks) re-encoded the choice.  A
:class:`DecodeSession` pairs model weights with a
:class:`repro.serve.kv_cache.CacheLayout` and exposes the whole decode
lifecycle as four calls:

  ``prefill(rid, prompt)``        full-prompt prefill into the layout
  ``prefill_chunk(rid, ...)``     one chunked-prefill slice (paged)
  ``step(tokens, index, ...)``    K >= 1 tokens per row, any layout
  ``snapshot() / restore(...)``   recurrent-state rollback

``step`` is the single write primitive: ``tokens`` is (B, K) with
K >= 1, so a speculative verify (K tokens at once) and a classic decode
(K = 1) are the same call, on dense rows, paged pools, and hybrid
stacks alike.  ``snapshot``/``restore`` bound what speculation can
break: attention KV never needs rollback (stale positions are causally
masked and overwritten), so a snapshot is exactly the recurrent leaves
— empty, and free, for attention-only models.

The jitted executables are module-level and keyed by the (hashable)
config, so scheduler, drafter, engine, and benchmark sessions of the
same model share every compile.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serve.kv_cache import CacheLayout, PagedLayout

# module-level jits (config is a hashable frozen dataclass): compiled
# executables are shared across DecodeSession instances, so spinning up
# a server — or a target + drafter pair — never re-pays compilation


@partial(jax.jit, static_argnums=(1,))
def _prefill_fn(params, cfg, toks, last_pos):
    return lm.lm_prefill(params, cfg, {"tokens": toks}, last_pos=last_pos)


@partial(jax.jit, static_argnums=(1,), donate_argnums=(3,))
def _chunk_fn(params, cfg, toks, cache, tables, hist, plen, last_pos):
    return lm.lm_prefill(params, cfg, {"tokens": toks}, last_pos=last_pos,
                         cache=cache, tables=tables, hist_len=hist,
                         prompt_len=plen)


@partial(jax.jit, static_argnums=(1,), donate_argnums=(3,))
def _step_fn(params, cfg, tokens, cache, index, valid):
    return lm.lm_decode(params, cfg, tokens, cache, index, valid=valid)


@partial(jax.jit, static_argnums=(1,), donate_argnums=(3,))
def _step_tables_fn(params, cfg, tokens, cache, index, tables, valid):
    return lm.lm_decode(params, cfg, tokens, cache, index, tables=tables,
                        valid=valid)


def _draft_unroll(params, cfg, tok0, cache, index, valid, steps, tables):
    """`steps` single-token decodes in ONE jitted dispatch, each feeding
    the next token by on-device greedy argmax — the fused drafter round.
    Step t is real for row b iff ``t < valid[b]`` (null-routed paged
    writes + frozen recurrent state beyond, exactly like a masked
    multi-token step).  Returns (logits (B, steps, V), the tokens
    actually fed (B, steps), new cache)."""
    tok = tok0
    fed, logits_all = [], []
    for t in range(steps):
        valid_t = jnp.minimum(jnp.maximum(valid - t, 0), 1)
        if tables is None:
            idx_t = index + t
            logits, cache = lm.lm_decode(params, cfg, tok, cache, idx_t,
                                         valid=valid_t)
        else:
            idx_t = jnp.where(index >= 0, index + t, -1)
            logits, cache = lm.lm_decode(params, cfg, tok, cache, idx_t,
                                         tables=tables, valid=valid_t)
        fed.append(tok[:, 0])
        logits_all.append(logits[:, 0])
        # greedy device feed; the scheduler redraws from the returned
        # logits with the request's true sampling function afterwards
        tok = jnp.argmax(logits[:, 0].astype(jnp.float32),
                         axis=-1).astype(jnp.int32)[:, None]
    return (jnp.stack(logits_all, axis=1), jnp.stack(fed, axis=1), cache)


@partial(jax.jit, static_argnums=(1, 6), donate_argnums=(3,))
def _draft_fn(params, cfg, tok0, cache, index, valid, steps):
    return _draft_unroll(params, cfg, tok0, cache, index, valid, steps,
                         None)


@partial(jax.jit, static_argnums=(1, 6), donate_argnums=(3,))
def _draft_tables_fn(params, cfg, tok0, cache, index, valid, steps,
                     tables):
    return _draft_unroll(params, cfg, tok0, cache, index, valid, steps,
                         tables)


class DecodeSession:
    """Weights + a cache layout, driven through one decode API.

    The session owns the jit boundaries and the cache pytree rebinding
    (every step donates the layout's cache and rebinds the result);
    request/slot lifecycle stays on ``session.layout`` so schedulers
    keep their admission logic while never touching a model entry point
    directly.
    """

    def __init__(self, cfg: ModelConfig, params, layout: CacheLayout):
        self.cfg = cfg
        self.params = params
        self.layout = layout

    @property
    def paged(self) -> bool:
        """True when the KV cache is the paged (scattered-page) layout."""
        return isinstance(self.layout, PagedLayout)

    def set_params(self, params) -> None:
        """Hot-swap weights (cache layout depends only on the config)."""
        self.params = params

    # -- jit indirection ---------------------------------------------------
    # Every dispatch goes through one of these hooks so a subclass can
    # swap in DIFFERENT jitted executables (the serving mesh binds
    # mesh-dedicated jits with the Mesh as a static arg) while the
    # host-side marshalling above/below stays in exactly one place.
    def _call_prefill(self, *args):
        return _prefill_fn(*args)

    def _call_chunk(self, *args):
        return _chunk_fn(*args)

    def _call_step(self, *args):
        return _step_fn(*args)

    def _call_step_tables(self, *args):
        return _step_tables_fn(*args)

    def _call_draft(self, *args):
        return _draft_fn(*args)

    def _call_draft_tables(self, *args):
        return _draft_tables_fn(*args)

    # -- prefill -----------------------------------------------------------
    def prefill(self, rid, prompt: np.ndarray,
                bucket: Optional[int] = None) -> jax.Array:
        """Full-prompt prefill for ONE request, written into its
        slot/pages.  ``bucket`` right-pads the prompt to a shape bucket
        (attention-only stacks; logits still read at the true last
        token); None prefills at exact length (recurrent families —
        padding would poison their state).  Returns the last-token
        logits row (V,) as float32, still on device (the caller copies
        it to the host when it needs it).
        """
        P = int(len(prompt))
        L = bucket or P
        toks = np.zeros((1, L), np.int32)
        toks[0, :P] = prompt
        logits, cache = self._call_prefill(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([P - 1], jnp.int32))
        if self.paged:
            self.layout.insert_prefill(rid, cache, P)
        else:
            self.layout.insert(rid, cache)
        return logits[0, -1].astype(jnp.float32)

    def prefill_batch(self, tokens: jax.Array) -> jax.Array:
        """Uniform-length batch prefill filling EVERY slot row (the
        engine path; slot layouts only).  Returns logits (B, 1, V)."""
        logits, cache = self._call_prefill(self.params, self.cfg,
                                           tokens, None)
        self.layout.insert_batch(cache)
        return logits

    def prefill_chunk(self, rid, chunk: np.ndarray, hist_len: int,
                      prompt_len: int, chunk_bucket: int,
                      width: int) -> jax.Array:
        """One chunked-prefill slice scattered into `rid`'s pages.

        chunk: the real tokens of this slice (right-padded to
        ``chunk_bucket`` here); hist_len: prompt tokens already
        prefilled; width: block-table columns to expose (pow2-bucketed
        by the caller).  Returns the slice's last-real-token logits row
        (V,) as float32, still on device — only meaningful on the final
        slice, so only then worth copying to the host.
        """
        n = int(len(chunk))
        toks = np.zeros((1, chunk_bucket), np.int32)
        toks[0, :n] = chunk
        slot = self.layout.slot_of(rid)
        logits, self.layout.cache = self._call_chunk(
            self.params, self.cfg, jnp.asarray(toks), self.layout.cache,
            jnp.asarray(self.layout.tables[slot:slot + 1, :width]),
            jnp.int32(hist_len), jnp.int32(prompt_len),
            jnp.asarray([n - 1], jnp.int32))
        return logits[0, -1].astype(jnp.float32)

    # -- decode ------------------------------------------------------------
    def step(self, tokens: np.ndarray, index: np.ndarray,
             valid: Optional[np.ndarray] = None,
             width: Optional[int] = None,
             rows: Optional[np.ndarray] = None,
             tables: Optional[np.ndarray] = None) -> jax.Array:
        """One decode/verify step: K >= 1 tokens per row.

        tokens: (B, K) int32; index: (B,) first-token write positions
        (-1 = idle row on paged layouts); valid: optional (B,) real
        token counts (speculative verify / rollback replay); width:
        block-table columns (paged; pow2-bucketed by the caller); rows:
        restrict a paged step to these slots (ragged grouping — only
        when ``layout.supports_row_subset``); tables: explicit
        block-table array overriding the layout's (padded group calls).
        Returns logits (B, K, V) still on device (callers cast/copy).
        """
        tok = jnp.asarray(tokens, jnp.int32)
        idx = jnp.asarray(index, jnp.int32)
        v = None if valid is None else jnp.asarray(valid, jnp.int32)
        if self.paged:
            if tables is None:
                tables = self.layout.step_kwargs(width=width,
                                                 rows=rows)["tables"]
            else:
                tables = jnp.asarray(tables)
            logits, self.layout.cache = self._call_step_tables(
                self.params, self.cfg, tok, self.layout.cache, idx,
                tables, v)
        else:
            logits, self.layout.cache = self._call_step(
                self.params, self.cfg, tok, self.layout.cache, idx, v)
        return logits

    def draft_block(self, tok0: np.ndarray, index: np.ndarray,
                    steps: int, valid: Optional[np.ndarray] = None,
                    width: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array]:
        """Fused drafter round: ``steps`` unrolled single-token decodes
        in ONE dispatch, each feeding the next token by on-device
        greedy argmax.

        tok0: (B, 1) the pending token per row; index: (B,) its write
        position (-1 = idle row on paged layouts); valid: (B,) real
        steps per row (rows freeze beyond, like a masked multi-token
        step); width: block-table columns (paged).  Returns (logits
        (B, steps, V), fed tokens (B, steps)) still on device — the
        caller redraws proposals from the logits with the request's
        real sampling function and repairs the cache where its samples
        diverge from the greedy feed.
        """
        tok = jnp.asarray(tok0, jnp.int32)
        idx = jnp.asarray(index, jnp.int32)
        B = tok.shape[0]
        v = jnp.full((B,), steps, jnp.int32) if valid is None \
            else jnp.asarray(valid, jnp.int32)
        if self.paged:
            tables = self.layout.step_kwargs(width=width)["tables"]
            logits, fed, self.layout.cache = self._call_draft_tables(
                self.params, self.cfg, tok, self.layout.cache, idx, v,
                steps, tables)
        else:
            logits, fed, self.layout.cache = self._call_draft(
                self.params, self.cfg, tok, self.layout.cache, idx, v,
                steps)
        return logits, fed

    # -- rollback ----------------------------------------------------------
    def snapshot(self) -> Tuple[jax.Array, ...]:
        """Copy of the recurrent leaves (empty for attention-only
        stacks — their rollback is free)."""
        return self.layout.snapshot()

    def restore(self, snap: Tuple[jax.Array, ...], rows) -> None:
        """Roll slots with ``rows[b] == True`` back to ``snap``; pair
        with a ``valid``-masked replay :meth:`step` to rebuild the
        accepted prefix."""
        self.layout.restore(snap, rows)
