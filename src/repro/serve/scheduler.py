"""Continuous-batching LM serving scheduler over a PAGED KV cache.

The serving analogue of ``core/tournament.py``'s training orchestrator:
a request queue in front of a slot-based decode batch backed by ONE
preallocated :class:`repro.serve.kv_cache.PagedLayout` (or the PR-2
dense :class:`~repro.serve.kv_cache.SlotLayout` with
``layout="dense"``, kept as the benchmark baseline).  ALL model calls
go through one :class:`repro.serve.session.DecodeSession` per set of
weights — the scheduler never picks a decode entry point by layout.

Per scheduler step:

  1. *hot-swap check* — if a :class:`repro.serve.registry.ModelRegistry`
     is attached, poll it every ``watch_every`` steps.
     ``swap_mode="immediate"`` swaps a newer tournament winner in
     between steps (in-flight KV caches remain valid: cache layout
     depends only on the config, not the weights);
     ``swap_mode="drain"`` holds the new weights pending, stops
     admitting, lets every in-flight request finish on the old weights,
     then swaps and resumes — strict per-request weight reproducibility.
  2. *admission* — pop queued requests while a slot AND a full
     token-budget page reservation (prompt + max new tokens) are
     available.  On the paged layout a prompt whose prefix is already
     resident (another live request's registered prompt pages) maps
     those pages read-only into its block table and skips their
     prefill compute entirely (copy-on-admit prefix sharing; with
     ``pin_prefix=True`` registered prompt pages additionally survive
     idle periods in an eviction-priority tier).
  3. *chunked prefill* — attention-only stacks prefill in
     ``prefill_chunk``-token slices, one slice per prefilling request
     per step, interleaved with decode, so admitting a long prompt
     never stalls in-flight decodes.  Recurrent families (mamba /
     xLSTM) prefill one-shot at exact length — their state cannot
     resume mid-prompt — and scatter into pages afterwards.
  4. *decode* — batched ``session.step`` over the in-flight rows.
     Plain rounds write one token per row; with a drafter attached
     (``draft_params`` + ``spec_tokens K``) each round runs
     **population speculative decoding**: the drafter (an
     earlier/smaller LTFB population checkpoint) proposes K tokens per
     row, the target verifies all K + 1 in ONE multi-token
     ``session.step``, the per-row accepted prefix is kept, and
     rejected rows roll recurrent state back via
     ``session.restore`` + a ``valid``-masked replay.  At any
     temperature the output is token-identical to target-only decoding
     (every token is drawn on the device by
     :func:`repro.serve.sampling.sample_rows`, a deterministic function
     of the target logits, the request's seed and the token's
     position).  On CPU the jnp gather oracle pays the full
     bucketed table width per row, so when one row's pow2 width is
     >= 4x everyone else's (the one-long-request pathology) the round
     splits into (narrow, wide) groups stepped separately — the long
     request no longer widens every row's gather, while the common
     case stays a single dispatch.
  5. *completion* — requests hitting EOS or their token budget free
     their slot + page refs immediately; the batch never stalls on its
     slowest member.

``policy="static"`` degrades admission to classic static batching
(admit only when the pool is empty) — the baseline the fig14 benchmark
compares against, sharing every compiled kernel with the continuous
path.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serve.kv_cache import PagedLayout, SlotLayout, blocks_for
from repro.serve.metrics import ServeStats
from repro.serve.sampling import sample_rows, seed_words
from repro.serve.session import DecodeSession
from repro.serve.telemetry import ServeTelemetry, log_event


class Overloaded(RuntimeError):
    """Raised by :meth:`Scheduler.submit` when the request queue is at
    its ``max_queue`` bound — the load-shedding signal the gateway maps
    to HTTP 429 instead of queueing unboundedly."""


@dataclass
class Request:
    """One generation request.

    ``prompt`` is a (P,) int32 token-id array; ``max_new`` bounds the
    generated tokens; ``temperature > 0`` requires ``seed``, an int in
    ``[0, 2**64)`` (sampling runs on the device and is deterministic in
    ``(seed, ntok_base + ntok)``, see :mod:`repro.serve.sampling`).  The
    optional deadlines are SLO declarations in milliseconds: a queued
    request whose ``ttft_deadline_ms`` has already expired is shed by
    :meth:`Scheduler.shed_expired` instead of admitted late, and a
    completed request that missed its TTFT/TPOT deadline increments
    the corresponding ``[serve]`` miss counter.

    ``ntok_base`` offsets the sampler's stream position: a journal
    resume re-submits a request with ``k`` already-emitted tokens folded
    into the prompt and ``ntok_base=k``, so its first new sample draws
    at position ``k`` — exactly the draw the uninterrupted run would
    have made (see ``repro.serve.journal``).  ``idem_key`` carries the
    gateway's ``Idempotency-Key`` header into the journal so client
    retries after a restart don't double-admit.
    """

    rid: Any
    prompt: np.ndarray              # (P,) int32 token ids
    max_new: int
    eos_id: Optional[int] = None
    temperature: float = 0.0
    seed: Optional[int] = None
    ttft_deadline_ms: Optional[float] = None   # first token due (ms)
    tpot_deadline_ms: Optional[float] = None   # mean ms/token budget
    ntok_base: int = 0              # rng-stream offset (journal resume)
    idem_key: Optional[str] = None  # gateway Idempotency-Key, journaled

    @property
    def prompt_len(self) -> int:
        """Prompt length P in tokens."""
        return int(len(self.prompt))


@dataclass
class _Active:
    req: Request
    slot: int
    ntok: int = 0                   # tokens generated so far
    pf_pos: int = 0                 # prompt tokens prefilled so far
    tokens: List[int] = field(default_factory=list)
    submit_t: float = 0.0
    first_token_t: Optional[float] = None


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class Scheduler:
    """Continuous-batching scheduler over a paged KV-cache pool."""

    def __init__(self, cfg: ModelConfig, params, num_slots: int = 8,
                 max_len: int = 1024, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 layout: str = "paged",
                 policy: str = "continuous",
                 prefill_chunk: int = 0,
                 prefix_sharing: bool = True,
                 pin_prefix: bool = False,
                 max_prefills_per_step: int = 1,
                 min_prefill_bucket: int = 8,
                 registry=None, watch_every: int = 0,
                 swap_mode: str = "immediate",
                 draft_params=None, spec_tokens: int = 0,
                 draft_cfg: Optional[ModelConfig] = None,
                 spec_fused: bool = True,
                 spec_adapt: bool = False,
                 max_queue: Optional[int] = None,
                 telemetry: bool = True,
                 trace_capacity: int = 8192,
                 journal=None, faults=None, arena=None):
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r}")
        if layout not in ("paged", "dense"):
            raise ValueError(f"unknown layout {layout!r}")
        if swap_mode not in ("immediate", "drain"):
            raise ValueError(f"unknown swap_mode {swap_mode!r}")
        if cfg.family == "vlm":
            raise ValueError(
                "serving scheduler supports token-input families only "
                "(vlm prompts need precomputed embeddings)")
        if spec_tokens > 0 and draft_params is None:
            raise ValueError("spec_tokens > 0 needs draft_params "
                             "(the population drafter)")
        self.cfg = cfg
        self.policy = policy
        self.layout = layout
        self.paged = layout == "paged"
        self.prefill_chunk = int(prefill_chunk)
        self.max_prefills_per_step = max_prefills_per_step
        self.min_prefill_bucket = min_prefill_bucket
        self.registry = registry
        self.watch_every = watch_every
        self.swap_mode = swap_mode
        self.spec_tokens = int(spec_tokens) if draft_params is not None \
            else 0
        self.spec_fused = bool(spec_fused)
        self.spec_adapt = bool(spec_adapt)
        self.max_queue = max_queue if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None)")
        # the drafter may be a SMALLER arch than the target (per-session
        # configs); vocab compatibility is a hard precondition — draft
        # token ids index the target's embedding
        self.draft_cfg = draft_cfg if draft_cfg is not None else cfg
        if draft_params is not None and self.draft_cfg is not cfg:
            from repro.serve.registry import check_draft_compat
            check_draft_compat(cfg, self.draft_cfg)
        if max_seq is not None and max_seq != max_len and not self.paged:
            raise ValueError("layout='dense' caps requests at max_len")
        n_blocks = num_blocks if num_blocks is not None \
            else num_slots * blocks_for(max_len, block_size)
        # geometry the layout factory reads (subclasses reuse it when
        # building mesh-sharded pools)
        self._geom = {"num_slots": num_slots, "max_len": max_len,
                      "block_size": block_size, "n_blocks": n_blocks,
                      "num_blocks": num_blocks,
                      "max_seq": max_seq or max_len,
                      "pin_prefix": pin_prefix}
        self.pool = self._make_layout(cfg)
        self.max_seq = self.pool.max_seq if self.paged else max_len
        # ALL model calls go through sessions; the drafter is a second
        # session over its own (mirror-geometry) pool — same decode API
        self.session = self._make_session(cfg, params, self.pool)
        self.draft: Optional[DecodeSession] = None
        if draft_params is not None:
            self.draft = self._make_session(
                self.draft_cfg, draft_params,
                self._make_layout(self.draft_cfg))
        # right-padding prompts is only sound for pure-attention stacks:
        # recurrent layers (mamba/xLSTM) would fold padding into their
        # state, so those families prefill at exact prompt length
        # (one compile per distinct length instead of per bucket) —
        # and one-shot: chunked prefill needs mid-prompt resume, which
        # only the paged attention path supports.
        self._can_pad = all(s.kind in lm.ATTENTION_KINDS
                            for s in lm.layer_specs(cfg))
        self._draft_can_pad = all(
            s.kind in lm.ATTENTION_KINDS
            for s in lm.layer_specs(self.draft_cfg))
        self._chunked = self.paged and self._can_pad
        self.prefix_sharing = bool(prefix_sharing) and self._chunked
        # ragged gather-width grouping only pays on the CPU oracle (the
        # Pallas kernel already skips per-row via pl.when) and needs
        # every cache leaf slot-free (attention-only paged stacks)
        self._group_decode = self.paged and self.pool.supports_row_subset \
            and jax.default_backend() != "tpu"
        self.queue: deque[Request] = deque()
        self.active: Dict[Any, _Active] = {}
        self.prefilling: Dict[Any, _Active] = {}
        # one-shot prefills admitted this step, run AFTER the admission
        # phase completes (on a mesh: after host 0's decisions are
        # broadcast — device work must be identical on every host)
        self._pending_onepass: List[_Active] = []
        self._pending_draft: List[Request] = []
        self._by_slot: Dict[int, _Active] = {}
        self._next_token = np.zeros((num_slots,), np.int32)
        # paged decode uses -1 as the "row holds no request" sentinel
        # (KV writes route to the null page); dense keeps 0 (the row is
        # the slot's own, writes are harmless)
        self._idle_index = -1 if self.paged else 0
        self._index = np.full((num_slots,), self._idle_index, np.int32)
        # per-row speculative depth (spec_adapt): proposals offered next
        # round for the request in each slot, adapted from its accept
        # history within [1, spec_tokens]
        self._spec_k = np.full((num_slots,), max(self.spec_tokens, 1),
                               np.int32)
        self.spec_k_by_rid: Dict[Any, int] = {}
        self.results: Dict[Any, np.ndarray] = {}
        self.stats = ServeStats(slots=num_slots)
        if self.paged and cfg.mla is not None:
            self.stats.latent_cache_bytes_per_token = \
                self.pool.bytes_per_token
        # request tracing + phase attribution + profiler window;
        # telemetry=False keeps the counters but drops the spans
        self.telemetry = ServeTelemetry(enabled=telemetry,
                                        trace_capacity=trace_capacity)
        # rank -> latest follower stats snapshot (mesh aggregation;
        # stays {} on a single-process scheduler)
        self.remote_stats: Dict[int, dict] = {}
        # fault tolerance: an optional write-ahead RequestJournal (one
        # fsync per step, batched below) and an optional FaultInjector
        # fired at the top of each step
        self.journal = journal
        self.faults = faults
        # online LTFB: the resident population roster + tournament
        # (serve/arena.py); drives drafter rotation and champion
        # promotions from inside step()
        self.arena = arena
        if arena is not None and (self.draft is None
                                  or self.spec_tokens <= 0):
            raise ValueError(
                "an online-LTFB arena scores challengers through the "
                "speculative path: pass draft_params (the active "
                "challenger's weights) and spec_tokens > 0")
        self._journal_tokens: Dict[Any, List[int]] = {}
        self._journal_finished: List[Any] = []
        self._pending_params = None
        self._head_share = None
        self._step_count = 0

    # -- construction hooks (the mesh scheduler overrides these) ------------
    def _make_layout(self, cfg: ModelConfig):
        g = self._geom
        if self.paged:
            return PagedLayout(cfg, g["num_slots"], g["n_blocks"],
                               block_size=g["block_size"],
                               max_seq=g["max_seq"],
                               pin_prefix=g["pin_prefix"])
        return SlotLayout(cfg, g["num_slots"], g["max_len"],
                          block_size=g["block_size"],
                          num_blocks=g["num_blocks"])

    def _make_session(self, cfg: ModelConfig, params,
                      layout) -> DecodeSession:
        return DecodeSession(cfg, params, layout)

    @property
    def params(self):
        """The TARGET weights currently serving (the session's tree)."""
        return self.session.params

    # -- request intake ----------------------------------------------------
    def _reject(self, msg: str):
        self.stats.rejected += 1
        raise ValueError(msg)

    def submit(self, req: Request) -> None:
        """Validate + enqueue a request (host-side only, non-blocking).

        Raises :class:`Overloaded` when the queue is at ``max_queue``
        (the caller should shed/backpressure, not retry in a loop) and
        ``ValueError`` for malformed requests (duplicate rid, empty
        prompt, budget over the pool ceiling, missing seed); both are
        counted in the ``[serve]`` stats.  Admission to the decode
        batch happens later, inside :meth:`step`.
        """
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.stats.shed_overload += 1
            raise Overloaded(
                f"request queue is at max_queue={self.max_queue}; "
                f"request {req.rid!r} shed (retry with backoff)")
        total = req.prompt_len + req.max_new
        if req.rid in self.active or req.rid in self.prefilling or \
                req.rid in self.results or \
                any(q.rid == req.rid for q in self.queue):
            self._reject(f"duplicate request id {req.rid!r}")
        if req.prompt_len < 1 or req.max_new < 1:
            self._reject("need a non-empty prompt and max_new >= 1")
        if total > self.max_seq:
            self._reject(
                f"request {req.rid!r} needs {total} tokens > the "
                f"per-request cap (max_len/max_seq {self.max_seq})")
        if blocks_for(total, self.pool.blocks.block_size) \
                > self.pool.blocks.num_blocks:
            self._reject(
                f"request {req.rid!r} exceeds the pool's total token "
                "budget")
        if req.temperature > 0.0 and req.seed is None:
            self._reject(
                f"request {req.rid!r}: temperature > 0 requires a seed "
                "(refusing to silently fall back to greedy)")
        if req.seed is not None:
            try:
                seed_words(req.seed)
            except (TypeError, ValueError) as e:
                self._reject(f"request {req.rid!r}: {e}")
        self.stats.submitted += 1
        if self.journal is not None:
            self.journal.record_submit(req)
        req._submit_t = time.perf_counter()   # TTFT includes queueing delay
        self.queue.append(req)
        self.telemetry.req_instant(req.rid, "enqueue", t=req._submit_t,
                                   queue_depth=len(self.queue))

    # -- scheduling ---------------------------------------------------------
    def _bucket(self, n: int, cap: Optional[int] = None,
                can_pad: Optional[bool] = None) -> int:
        if not (self._can_pad if can_pad is None else can_pad):
            return n
        cap = cap or self.max_seq
        return min(max(self.min_prefill_bucket, _next_pow2(n)), cap)

    def _can_admit_head(self) -> bool:
        req = self.queue[0]
        total = req.prompt_len + req.max_new
        if self.draft is not None and \
                not self._pool_can_admit(self.draft.layout, total):
            return False
        return self._pool_can_admit(self.pool, total, head=True)

    def _pool_can_admit(self, pool, total: int, head: bool = False) -> bool:
        if not self.paged:
            return pool.can_admit(total)
        if not pool.free_slots:         # skip prefix hashing when full
            return False
        shared = ()
        if head:
            self._head_share = None
            if self.prefix_sharing:
                # cache the match: _admit reuses it instead of re-hashing
                req = self.queue[0]
                self._head_share = (req.rid,
                                    pool.find_shared_prefix(req.prompt))
                shared = self._head_share[1][0]
        return pool.can_admit(total, shared_pages=shared)

    def _admit(self, req: Request) -> None:
        """Claim slot + pages (host-side accounting ONLY — the prefill
        dispatch is deferred to :meth:`_prefill_phase`, so on a mesh
        every host issues identical device work after the admission
        decisions are broadcast)."""
        P = req.prompt_len
        total = P + req.max_new
        now = time.perf_counter()
        self.telemetry.req_span(req.rid, "queued",
                                getattr(req, "_submit_t", None), now)
        if not self.paged:
            slot = self.pool.admit(req.rid, total)
            self._admit_draft(req, slot, total)
            act = _Active(req=req, slot=slot, submit_t=getattr(
                req, "_submit_t", time.perf_counter()))
            self._spec_k[slot] = max(self.spec_tokens, 1)
            self._pending_onepass.append(act)
            self.telemetry.req_instant(req.rid, "admit", t=now, slot=slot)
            return
        head = getattr(self, "_head_share", None)
        shared = head[1] if head is not None and head[0] == req.rid \
            else None
        self._head_share = None
        slot, shared_len = self.pool.admit(
            req.rid, total, shared=shared,
            prompt=req.prompt if self.prefix_sharing else None)
        self._admit_draft(req, slot, total)
        act = _Active(req=req, slot=slot, pf_pos=shared_len,
                      submit_t=getattr(req, "_submit_t",
                                       time.perf_counter()))
        self._spec_k[slot] = max(self.spec_tokens, 1)
        self.telemetry.req_instant(req.rid, "admit", t=now, slot=slot,
                                   shared_prefix_tokens=shared_len)
        if self._chunked:
            # chunk slices run in _prefill_step, interleaved with decode
            self.prefilling[req.rid] = act
        else:
            self._pending_onepass.append(act)

    def _admit_draft(self, req: Request, slot: int, total: int) -> None:
        """Mirror an admission into the drafter's pool at the SAME slot
        (the two decode batches must stay row-aligned); the drafter's
        one-shot prompt prefill is deferred with the target's."""
        if self.draft is None:
            return
        if self.paged:
            d_slot, _ = self.draft.layout.admit(req.rid, total, slot=slot)
        else:
            d_slot = self.draft.layout.admit(req.rid, total, slot=slot)
        assert d_slot == slot, (d_slot, slot)
        self._pending_draft.append(req)

    def _prefill_draft(self, req: Request) -> None:
        bucket = self._bucket(req.prompt_len,
                              can_pad=self._draft_can_pad) \
            if self._draft_can_pad else None
        with self.telemetry.span("prefill.dispatch"):
            self.draft.prefill(req.rid, req.prompt, bucket=bucket)

    def _prefill_dense(self, act: _Active) -> None:
        req = act.req
        P = req.prompt_len
        bucket = self._bucket(P)
        t0 = time.perf_counter()
        with self.telemetry.span("prefill.dispatch"):
            last = self.session.prefill(req.rid, req.prompt, bucket=bucket)
        self.telemetry.req_span(req.rid, "prefill", t0, time.perf_counter(),
                                tokens=P, bucket=bucket)
        self.stats.prefills += 1
        self.stats.prefill_tokens += P
        self.stats.padded_prefill_tokens += bucket
        self._start_decoding(act, last)

    def _prefill_onepass_paged(self, act: _Active) -> None:
        """Exact-length one-shot prefill + page scatter (recurrent /
        hybrid families: their state cannot resume mid-prompt)."""
        req = act.req
        P = req.prompt_len
        t0 = time.perf_counter()
        with self.telemetry.span("prefill.dispatch"):
            last = self.session.prefill(req.rid, req.prompt, bucket=None)
        self.telemetry.req_span(req.rid, "prefill", t0, time.perf_counter(),
                                tokens=P)
        self.stats.prefills += 1
        self.stats.prefill_tokens += P
        self.stats.padded_prefill_tokens += P
        self._start_decoding(act, last)

    def _prefill_step(self) -> None:
        """Advance chunked prefills: one chunk per prefilling request,
        at most ``max_prefills_per_step`` chunk calls per step."""
        done = 0
        for act in list(self.prefilling.values()):
            if done >= self.max_prefills_per_step:
                break
            self._prefill_chunk_once(act)
            done += 1

    def _prefill_chunk_once(self, act: _Active) -> None:
        tel = self.telemetry
        req = act.req
        P = req.prompt_len
        # one-shot (prefill_chunk=0) still buckets the chunk size, so a
        # mixed-length trace compiles per pow2 bucket, not per length
        chunk = self.prefill_chunk if self.prefill_chunk > 0 \
            else self._bucket(P)
        n = min(chunk, P - act.pf_pos)
        final = act.pf_pos + n >= P
        Cb = chunk if (not final or n == chunk) \
            else self._bucket(n, cap=chunk)
        with tel.span("kv.ensure"):
            self.pool.ensure(req.rid, act.pf_pos + n)
        W = self._table_bucket(act.pf_pos + n)
        t0 = time.perf_counter()
        with tel.span("prefill.dispatch"):
            last = self.session.prefill_chunk(
                req.rid, req.prompt[act.pf_pos:act.pf_pos + n],
                hist_len=act.pf_pos, prompt_len=P, chunk_bucket=Cb,
                width=W)
        tel.req_span(req.rid, "prefill_chunk", t0, time.perf_counter(),
                     tokens=n, pos=act.pf_pos, prompt_len=P)
        act.pf_pos += n
        self.stats.prefills += 1
        self.stats.prefill_chunks += 1
        self.stats.prefill_tokens += n
        self.stats.padded_prefill_tokens += Cb
        if self.prefix_sharing:
            # pages fully covered by prefilled prompt tokens are
            # immutable from here on — offer them to future admissions
            # immediately, not only when the whole prompt is done
            with tel.span("kv.register_prefix"):
                self.pool.register_prefix(req.rid, req.prompt[:act.pf_pos])
        if final:
            del self.prefilling[req.rid]
            self._start_decoding(act, last)

    def _start_decoding(self, act: _Active, last_logits: jax.Array) -> None:
        """Draw the first token off the prefill's device logits (V,) and
        move the request into the decode batch."""
        req = act.req
        self.active[req.rid] = act
        self._by_slot[act.slot] = act
        tok = int(self._draw(last_logits, [(0, act)], (1, 1))[0, 0])
        act.first_token_t = time.perf_counter()
        self.stats.ttft.append(act.first_token_t - act.submit_t)
        self.telemetry.req_instant(
            req.rid, "first_token", t=act.first_token_t,
            ttft_s=act.first_token_t - act.submit_t)
        self._accept_token(act, tok)

    def _draw(self, logits: jax.Array, picks: List[tuple],
              shape: Tuple[int, int], first: int = 0) -> np.ndarray:
        """Draw tokens on the device and copy back only their ids.

        ``picks`` holds ``(row, active request)`` pairs: row ``b`` of
        ``logits`` draws at the request's stream positions
        ``ntok_base + ntok + first + t`` for ``t < K``, with ``shape``
        ``(B, K)`` the rows and tokens per row the logits hold.  Rows not
        picked draw greedily and are ignored.  ``sample`` spans building
        the per-row inputs and dispatching the sampler, ``device_wait``
        the wait for the ids (and their copy).  Returns (B, K) int32.
        """
        tel = self.telemetry
        B, K = shape
        with tel.span("sample", rows=len(picks)):
            temp = np.zeros((B,), np.float32)
            seed = [0] * B
            base = np.zeros((B, 1), np.int64)
            for b, act in picks:
                req = act.req
                if req.temperature > 0.0:
                    temp[b] = req.temperature
                    seed[b] = req.seed
                base[b] = req.ntok_base + act.ntok + first
            ids = sample_rows(logits, temp, seed, base + np.arange(K))
        with tel.span("device_wait"):
            ids = np.asarray(ids)
        self.stats.sample_fetch_bytes += ids.nbytes
        return ids

    def _accept_token(self, act: _Active, tok: int) -> None:
        act.tokens.append(tok)
        act.ntok += 1
        self.stats.decode_tokens += 1
        if self.journal is not None:
            self._journal_tokens.setdefault(act.req.rid, []).append(tok)
        # write position of `tok`'s KV on the NEXT decode step
        self._index[act.slot] = act.req.prompt_len + act.ntok - 1
        self._next_token[act.slot] = tok
        done = act.ntok >= act.req.max_new or \
            (act.req.eos_id is not None and tok == act.req.eos_id)
        if done:
            self._finish(act)

    def _finish(self, act: _Active) -> None:
        rid = act.req.rid
        self.results[rid] = np.asarray(act.tokens, np.int32)
        if self.arena is not None:
            self.arena.record_finished(rid, act.req.prompt, act.tokens)
        if self.journal is not None:
            self._journal_finished.append(rid)
        if self.spec_adapt:
            self.spec_k_by_rid[rid] = int(self._spec_k[act.slot])
        self.stats.completed += 1
        now = time.perf_counter()
        self.stats.latency.append(now - act.submit_t)
        ttft = (act.first_token_t or now) - act.submit_t
        tpot = None
        if act.ntok > 1 and act.first_token_t is not None:
            tpot = (now - act.first_token_t) / (act.ntok - 1)
            self.stats.tpot.append(tpot)
        if act.req.ttft_deadline_ms is not None \
                and ttft * 1e3 > act.req.ttft_deadline_ms:
            self.stats.ttft_deadline_misses += 1
        if act.req.tpot_deadline_ms is not None and tpot is not None \
                and tpot * 1e3 > act.req.tpot_deadline_ms:
            self.stats.tpot_deadline_misses += 1
        self.telemetry.terminal(rid, "finish", t=now, ntok=act.ntok,
                                latency_s=now - act.submit_t)
        slot = self.pool.release(rid)
        if self.draft is not None:
            self.draft.layout.release(rid)
        del self.active[rid]
        del self._by_slot[slot]
        self._next_token[slot] = 0
        self._index[slot] = self._idle_index

    # -- cancellation / load shedding ---------------------------------------
    def cancel(self, rid) -> bool:
        """Drop a request wherever it is in its lifecycle.

        Queued requests leave the queue; prefilling/active requests
        release their slot and page reservations immediately (their
        partial tokens are NOT recorded in ``results`` — a streaming
        caller has already received them).  Returns True if the rid was
        found, False if it is unknown or already completed.  Host-side
        only and non-blocking; counted as ``cancelled``.
        """
        return self._cancel_now(rid, "cancel")

    def shed_expired(self) -> List[Any]:
        """Shed QUEUED requests whose TTFT deadline has already passed.

        A request that declared ``ttft_deadline_ms`` and has been
        queued longer than that can no longer meet its SLO, so
        admitting it wastes decode slots; it is dropped and counted as
        ``shed_deadline``.  Returns the shed rids (the gateway turns
        each into a 429-style deadline response).  In-flight requests
        are never shed — deadline misses there are counted at
        completion instead.
        """
        now = time.perf_counter()
        shed = [q.rid for q in self.queue
                if q.ttft_deadline_ms is not None
                and (now - getattr(q, "_submit_t", now)) * 1e3
                > q.ttft_deadline_ms]
        for rid in shed:
            self._cancel_now(rid, "deadline")
        return shed

    def _cancel_now(self, rid, reason: str) -> bool:
        """Immediately remove ``rid``; ``reason`` picks the counter
        ("cancel" -> cancelled, "deadline" -> shed_deadline)."""
        found = False
        for i, q in enumerate(self.queue):
            if q.rid == rid:
                del self.queue[i]
                found = True
                break
        if not found:
            act = self.active.get(rid) or self.prefilling.get(rid) or next(
                (a for a in self._pending_onepass if a.req.rid == rid),
                None)
            if act is None:
                return False
            # deferred device work for this rid must not run
            self._pending_onepass = [a for a in self._pending_onepass
                                     if a.req.rid != rid]
            self._pending_draft = [r for r in self._pending_draft
                                   if r.rid != rid]
            slot = self.pool.release(rid)
            if self.draft is not None:
                self.draft.layout.release(rid)
            self.active.pop(rid, None)
            self.prefilling.pop(rid, None)
            self._by_slot.pop(slot, None)
            self._next_token[slot] = 0
            self._index[slot] = self._idle_index
        if self._head_share is not None and self._head_share[0] == rid:
            self._head_share = None
        kind = "shed" if reason == "deadline" else "cancel"
        if self.journal is not None:
            self.journal.record_cancel(rid, reason)
            self._journal_tokens.pop(rid, None)
        self.telemetry.terminal(rid, kind, reason=reason)
        log_event(kind, rid=rid, reason=reason)
        if reason == "deadline":
            self.stats.shed_deadline += 1
        else:
            self.stats.cancelled += 1
        return True

    def set_params(self, params) -> None:
        """Hot-swap TARGET weights between steps (cache layout
        unchanged; the prefix cache is flushed — old-weight pages must
        not be shared into post-swap admissions).  The drafter keeps
        its own weights: draft tokens are only proposals, verified
        against the new target before acceptance."""
        self.session.set_params(params)
        if self.paged:
            self.pool.invalidate_prefix()
            self._head_share = None
        self.stats.hot_swaps += 1
        self.telemetry.event("hot_swap", step=self._step_count,
                             swaps=self.stats.hot_swaps)
        log_event("hot_swap", step=self._step_count,
                  swaps=self.stats.hot_swaps)

    @property
    def draining(self) -> bool:
        """True while new weights wait for in-flight requests to finish."""
        return self._pending_params is not None

    def _poll_registry(self) -> Optional[int]:
        """Poll for a newer winner; returns its step when one was
        loaded.  The ONLY nondeterministic scheduler decision (it reads
        the filesystem) — on a mesh, host 0 polls and broadcasts the
        answer so every host swaps to the same winner on the same step."""
        if self.registry is not None and self.watch_every > 0 \
                and self._step_count % self.watch_every == 0:
            found = self.registry.refresh()
            # mirror the registry's corrupt-swap rejections into the
            # step stats (exported at /metrics as a counter)
            self.stats.swap_rejected_corrupt = getattr(
                self.registry, "rejected_corrupt", 0)
            if found:
                return getattr(self.registry, "step", 0)
        return None

    def _apply_swap(self, winner: Optional[int]) -> None:
        """Deterministic half of the hot-swap: given host 0's poll
        result, apply/defer the swap per ``swap_mode``."""
        if winner is not None:
            if self.swap_mode == "drain" and (self.active
                                              or self.prefilling):
                self._pending_params = self.registry.params
            else:
                self._pending_params = None
                self.set_params(self.registry.params)
        if self._pending_params is not None and not self.active \
                and not self.prefilling:
            self.set_params(self._pending_params)
            self._pending_params = None

    def _maybe_hot_swap(self) -> None:
        self._apply_swap(self._poll_registry())

    # -- online LTFB arena (serve/arena.py) ----------------------------------
    def _arena_rotate(self) -> None:
        """Rotate the drafter session to the policy's pick for this
        step.  Pure function of (step, arena state) — every mesh host
        computes the same rotation without a broadcast."""
        if self.arena is None:
            return
        want = self.arena.drafter_for_step(self._step_count)
        if want != self.arena.active_drafter:
            self.arena.set_drafter(want)
            self.draft.set_params(self.arena.params[want])

    def _arena_decide(self) -> Optional[str]:
        """Host-0 half of a promotion: run the match evaluation, journal
        it, and — when the rule fires — run the transactional registry
        archive (checksum-verified) BEFORE anything mutates.  Returns
        the winner to broadcast, or None."""
        if self.arena is None:
            return None
        a = self.arena
        if a.forced is None and self._step_count % a.cfg.check_every != 0:
            return None
        winner = a.decide(self._step_count)
        self.stats.arena_matches = a.matches
        if self.journal is not None:
            self.journal.record_match(self._step_count, a.snapshot())
        if winner is None:
            return None
        prepared = a.prepare_promotion(winner)
        if prepared is None:
            # archive/export failed verification: abort, keep serving
            self.stats.swap_rejected_corrupt += 1
            return None
        return prepared

    def _arena_apply(self, winner: Optional[str]) -> None:
        """All-hosts half of a promotion: mutate arena state, journal
        the promotion (host 0; ordered BEFORE the weight swap so a torn
        record implies no swap), then hot-swap the target to the new
        champion — drain-aware, in-flight requests finish on the old
        weights."""
        if self.arena is None or winner is None:
            return
        a = self.arena
        loser = a.champion
        new_params = a.promote(winner, self._step_count)
        rec = a.last_promotion
        self.stats.arena_promotions = a.promotions
        if self.journal is not None:
            self.journal.record_promotion(
                self._step_count, winner, loser, rec["rate"],
                a.last_forced, a.snapshot())
        if self.swap_mode == "drain" and (self.active or self.prefilling):
            self._pending_params = new_params
        else:
            self._pending_params = None
            self.set_params(new_params)
        # the promotion recomputed the rotation; resync the drafter
        self.draft.set_params(a.params[a.active_drafter])
        log_event("arena_promotion", step=self._step_count,
                  winner=winner, loser=loser, rate=rec["rate"],
                  generation=a.generation)

    def arena_force(self, member: str) -> None:
        """Queue an admin promotion override (``POST /arena/promote``):
        the next match evaluation promotes ``member`` unconditionally —
        still through the transactional archive + drain-aware swap."""
        if self.arena is None:
            raise ValueError("no arena attached to this scheduler")
        if member not in self.arena.members:
            raise ValueError(
                f"unknown arena member {member!r}; roster is "
                f"{sorted(self.arena.members)}")
        self.arena.forced = member

    def _admission_phase(self) -> List[Any]:
        """Pop admissible queued requests and claim their slots/pages
        (host accounting only); returns the admitted rids in order —
        the decision record a mesh broadcasts."""
        admitted: List[Any] = []
        in_flight = bool(self.active or self.prefilling)
        if self.draining:
            return admitted
        if self.faults is not None \
                and self.faults.admission_blocked(self._step_count):
            return admitted       # injected pool exhaustion (oom@step)
        if self.policy == "static":
            if not in_flight:
                while self.queue and self._can_admit_head():
                    admitted.append(self.queue[0].rid)
                    self._admit(self.queue.popleft())
        else:
            while (len(admitted) < self.max_prefills_per_step
                   and self.queue and self._can_admit_head()):
                admitted.append(self.queue[0].rid)
                self._admit(self.queue.popleft())
        return admitted

    def _prefill_phase(self) -> None:
        """Run the device work admission deferred: drafter mirrors,
        one-shot prefills, then one round of chunked-prefill slices."""
        for req in self._pending_draft:
            self._prefill_draft(req)
        self._pending_draft.clear()
        for act in self._pending_onepass:
            if self.paged:
                self._prefill_onepass_paged(act)
            else:
                self._prefill_dense(act)
        self._pending_onepass.clear()
        if self.prefilling:
            self._prefill_step()

    def _decode_phase(self) -> None:
        if self.active:
            if self.spec_tokens > 0:
                self._spec_round()
            else:
                self._decode_round()

    def _timed_phases(self) -> None:
        """Run admission → prefill → decode, each in its phase span."""
        with self.telemetry.span("admit") as sp:
            sp.emit = bool(self._admission_phase())
        self._device_phases()

    def _device_phases(self) -> None:
        """Run prefill → decode, each in its phase span (in the
        profiler trace always; in the ring only when it had work)."""
        tel = self.telemetry
        with tel.span("prefill") as sp:
            sp.emit = bool(self._pending_draft or self._pending_onepass
                           or self.prefilling)
            self._prefill_phase()
        with tel.span("decode") as sp:
            sp.emit = bool(self.active)
            self._decode_phase()

    def profile_steps(self, steps: int, outdir: str) -> None:
        """Arm ``jax.profiler`` around the next ``steps`` scheduler
        steps (``--profile-steps`` / ``POST /debug/profile``): the
        trace starts at the next :meth:`step` and stops after the
        window closes; artifacts land under ``outdir``."""
        self.telemetry.arm_profile(steps, outdir)

    def _journal_step(self) -> None:
        """Commit this step's token emission + completions to the WAL
        — one batched write + fsync (see ``repro.serve.journal``)."""
        if self.journal is None:
            return
        with self.telemetry.span("journal"):
            self.journal.step_commit(self._journal_tokens,
                                     self._journal_finished)
        self._journal_tokens = {}
        self._journal_finished = []

    def step(self) -> None:
        """One scheduler iteration: hot-swap check, admission, chunked
        prefill, one batched decode (or speculative) round,
        completion."""
        self.stats.start()
        self.telemetry.step_begin(self._step_count + 1)
        if self.faults is not None:
            self.faults.on_step(self, self._step_count + 1)
        self._maybe_hot_swap()
        self._step_count += 1
        self._arena_rotate()
        self._arena_apply(self._arena_decide())
        self._timed_phases()
        self.stats.sample_step(len(self.queue),
                               len(self.active) + len(self.prefilling))
        self._journal_step()
        self.telemetry.step_end()

    # -- plain decode --------------------------------------------------------
    def _ensure_decode_pages(self, pool, last_token_pos: Dict[int, int]
                             ) -> None:
        """Materialize any page a row's upcoming writes land on.
        ``last_token_pos[slot]`` is the LAST write position of the
        round (ensure is idempotent; page boundaries are the only
        times new pages appear)."""
        bs = pool.block_size
        for act in self.active.values():
            first = int(self._index[act.slot])
            last = last_token_pos[act.slot]
            if first // bs != (first - 1) // bs or last // bs != first // bs:
                pool.ensure(act.req.rid, last + 1)

    def read_moe_counters(self) -> Optional[Dict[str, np.ndarray]]:
        """Copy the device's MoE routing counters into ``stats`` (one
        host copy, made when asked, never by a step) and return them:
        ``expert_tokens`` (layers, E), the real tokens each layer routed
        to each expert since the counters were last zeroed, and
        ``dropped_tokens`` (layers,).  None for stacks that keep none."""
        counts = self.pool.moe_counts() if self.paged else None
        if counts is not None:
            self.stats.moe_routed_tokens = [
                int(n) for n in counts["expert_tokens"].sum(axis=0)]
            self.stats.moe_dropped_tokens = int(
                counts["dropped_tokens"].sum())
        return counts

    def _width_split(self) -> List[tuple]:
        """Partition active rows for the ragged-gather fix: when one
        long request's pow2 table width is >= ``_SPLIT_RATIO``x every
        other row's, split the round into (narrow, wide) groups so the
        jnp oracle stops paying the long row's gather width for the
        whole batch.  Everything else stays ONE dispatch — per-call
        overhead beats gather savings until the spread is pathological.
        Returns [(width_bucket, [slots])]."""
        buckets = {act.slot: self._table_bucket(
            int(self._index[act.slot]) + 1)
            for act in self.active.values()}
        wide_w = max(buckets.values())
        narrow = [s for s, w in buckets.items() if w < wide_w]
        narrow_w = max((buckets[s] for s in narrow), default=0)
        if not self._group_decode or not narrow \
                or wide_w < self._SPLIT_RATIO * narrow_w:
            return [(wide_w, list(buckets))]
        wide = [s for s, w in buckets.items() if w == wide_w]
        return [(narrow_w, narrow), (wide_w, wide)]

    _SPLIT_RATIO = 4

    def _sample_and_accept(self, logits: jax.Array,
                           picks: List[tuple]) -> None:
        """One token for each ``(row, active request)`` of the (B, 1, V)
        device logits: draw them all, then ``accept`` them (finishing
        frees the slot in place)."""
        ids = self._draw(logits, picks, (logits.shape[0], 1))
        with self.telemetry.span("accept"):
            for b, act in picks:
                self._accept_token(act, int(ids[b, 0]))

    def _decode_round(self) -> None:
        tel = self.telemetry
        if self.paged:
            targets = {a.slot: int(self._index[a.slot])
                       for a in self.active.values()}
            with tel.span("kv.ensure"):
                self._ensure_decode_pages(self.pool, targets)
            groups = self._width_split()
        else:
            groups = [(0, None)]
        self.stats.decode_steps += 1
        if len(groups) == 1:
            # common path: one full-batch dispatch
            width = groups[0][0] if self.paged else None
            with tel.span("decode.dispatch"):
                logits = self.session.step(self._next_token[:, None],
                                           self._index, width=width)
            self.stats.decode_slot_steps += self.pool.num_slots
            self._sample_and_accept(logits, [(a.slot, a) for a in
                                             self.active.values()])
            return
        # ragged split: one subset dispatch per width group (row counts
        # pow2-bucketed so the compile count stays logarithmic)
        null = self.pool.null_page
        for W, slots in groups:
            n = min(_next_pow2(len(slots)), self.pool.num_slots)
            with tel.span("decode.dispatch"):
                tokens = np.zeros((n, 1), np.int32)
                index = np.full((n,), -1, np.int32)
                tables = np.full((n, W), null, np.int32)
                for i, s in enumerate(slots):
                    tokens[i, 0] = self._next_token[s]
                    index[i] = self._index[s]
                    tables[i] = self.pool.tables[s, :W]
                logits = self.session.step(tokens, index, tables=tables)
            self.stats.decode_slot_steps += n
            self.stats.ragged_splits += 1
            self._sample_and_accept(logits, [(i, self._by_slot[s])
                                             for i, s in enumerate(slots)
                                             if s in self._by_slot])

    # -- speculative decode --------------------------------------------------
    def _spec_round(self) -> None:
        """One population-speculative round.

        The drafter proposes up to ``spec_tokens`` tokens per row
        (``spec_adapt`` modulates the depth per row from its accept
        history); the target verifies the row's pending token plus all
        proposals in ONE (K+1)-token ``session.step``; the accepted
        prefix (matching proposals + one target token — correction or
        bonus) is kept, so every emitted token is a TARGET sample and
        the output stream is identical to target-only decoding.

        **Fused drafting** (``spec_fused``, the default): the whole
        draft block is ONE dispatch — ``session.draft_block`` unrolls
        K+1 single-token decodes on device, feeding each greedy argmax
        into the next — and the proposals are then REDRAWN from the
        returned logits with the request's real sampling function (the
        device sampler, keyed like the target's draws).  A round is 2
        model dispatches (draft + verify) instead of K+2.  At
        temperature 0 the redraw == the greedy feed, so the drafter's
        cache is exactly right; at temperature > 0 a redraw that
        diverges from the device feed leaves wrong tokens in the
        drafter's history, which the rollback below repairs (token
        identity is untouched either way — emitted tokens only ever
        come from the target).

        Rollback: the TARGET restores its recurrent snapshot + replays
        the accepted prefix when it kept fewer tokens than it fed
        (attention KV needs none: stale tail positions are causally
        masked and overwritten).  The DRAFTER additionally repairs
        rows whose device-fed block diverged from the redrawn
        block — a replay write for attention KV, restore + replay for
        recurrent state.
        """
        tel = self.telemetry
        B = self.pool.num_slots
        acts = list(self.active.values())
        t_rec = self.pool.has_recurrent
        d_rec = self.draft.layout.has_recurrent
        base = self._index.copy()
        # per-row cap: writes at base..base+cap-1 must stay inside the
        # prompt+max_new reservation (a cap-truncated row finishes this
        # round anyway)
        cap = np.zeros((B,), np.int32)
        for act in acts:
            k_row = int(self._spec_k[act.slot]) if self.spec_adapt \
                else self.spec_tokens
            cap[act.slot] = min(k_row + 1, act.req.max_new - act.ntok + 1)
        Kv = int(cap.max())
        if self.paged:
            targets = {a.slot: int(base[a.slot]) + int(cap[a.slot]) - 1
                       for a in acts}
            with tel.span("kv.ensure"):
                self._ensure_decode_pages(self.pool, targets)
                self._ensure_decode_pages(self.draft.layout, targets)
            W = self._table_bucket(int((base + cap).max()))
        else:
            W = None
        block = np.zeros((B, Kv), np.int32)
        block[:, 0] = self._next_token
        picks = [(act.slot, act) for act in acts]

        d_snap = self.draft.snapshot() if d_rec else ()
        with tel.span("draft", k=Kv - 1):
            if self.spec_fused:
                # -- fused draft: ONE dispatch for the whole block
                with tel.span("decode.dispatch"):
                    dlogits, dev = self.draft.draft_block(
                        self._next_token[:, None], base, Kv, valid=cap,
                        width=W)
                self.stats.spec_draft_steps += 1
                drawn = self._draw(dlogits, picks, (B, Kv))
                dev = np.asarray(dev)                       # (B, Kv)
                for act in acts:
                    s = act.slot
                    block[s, 1:cap[s]] = drawn[s, :cap[s] - 1]
            else:
                # -- sequential draft: Kv single-token steps (the last
                # feeds the final proposal so drafter and target caches
                # stay aligned when everything is accepted)
                for t in range(Kv):
                    valid_t = (cap > t).astype(np.int32)
                    idx_t = np.where(self._index >= 0, base + t,
                                     self._idle_index).astype(np.int32)
                    with tel.span("decode.dispatch"):
                        logits = self.draft.step(block[:, t:t + 1], idx_t,
                                                 valid=valid_t, width=W)
                    self.stats.spec_draft_steps += 1
                    if t + 1 >= Kv:
                        break
                    drawn = self._draw(logits, picks, (B, 1), first=t)
                    for act in acts:
                        s = act.slot
                        if t + 1 < cap[s]:
                            block[s, t + 1] = drawn[s, 0]
                dev = block          # the drafter was fed the drawn block

        # -- target: verify the whole block in one K-token step, and draw
        # the target's token at every position of it
        with tel.span("verify", k=Kv):
            t_snap = self.session.snapshot() if t_rec else ()
            with tel.span("decode.dispatch"):
                vlogits = self.session.step(block, base, valid=cap, width=W)
            target = self._draw(vlogits, picks, (B, Kv))
        self.stats.decode_steps += 1
        self.stats.spec_rounds += 1
        self.stats.decode_slot_steps += B

        # -- acceptance: longest matching prefix + one target token — the
        # target's draws up to the first that differs from the proposal,
        # an EOS or the row's budget
        fed_valid = np.zeros((B,), np.int32)
        with tel.span("accept"):
            for act in acts:
                s = act.slot
                c = min(int(cap[s]), act.req.max_new - act.ntok)
                appended = 0
                for t in range(c):
                    g = int(target[s, t])
                    self._accept_token(act, g)           # may finish
                    appended += 1
                    if g == act.req.eos_id or t + 1 >= c \
                            or g != int(block[s, t + 1]):
                        break
                fed_valid[s] = appended
                offered = max(0, int(cap[s]) - 1)
                accepted = max(0, appended - 1)
                self.stats.spec_draft_proposed += offered
                self.stats.spec_draft_accepted += accepted
                if self.arena is not None:
                    self.arena.record_spec(offered, accepted)
                if offered:
                    self.stats.spec_k_sum += offered
                    self.stats.spec_k_rows += 1
                    if self.spec_adapt:
                        self._adapt_depth(act, offered, accepted)

        # -- rollback
        rb_t = np.zeros((B,), bool)
        rep_t = np.zeros((B,), np.int32)
        rb_d = np.zeros((B,), bool)
        rep_d = np.zeros((B,), np.int32)
        for act in acts:
            s = act.slot
            if act.req.rid not in self.active:
                continue
            fed = int(fed_valid[s])
            if fed < cap[s]:
                # target kept fewer than it fed: recurrent state (if
                # any) rolls back to the accepted prefix
                rb_t[s] = True
                rep_t[s] = fed
            diverged = dev[s, 1:fed].tolist() != block[s, 1:fed].tolist()
            if diverged or (d_rec and fed < cap[s]):
                rb_d[s] = True
                rep_d[s] = fed
        if t_rec and rb_t.any():
            self.session.restore(t_snap, rb_t)
            with tel.span("decode.dispatch"):
                self.session.step(block, base, valid=rep_t, width=W)
            self.stats.spec_replays += 1
        if rb_d.any():
            if d_rec:
                self.draft.restore(d_snap, rb_d)
            with tel.span("decode.dispatch"):
                self.draft.step(block, base, valid=rep_d, width=W)
            self.stats.spec_replays += 1

    def _adapt_depth(self, act: _Active, offered: int,
                     accepted: int) -> None:
        """Per-row speculative depth policy (``--spec-adapt``):
        additive increase on a fully accepted block, halve on a
        complete rejection, otherwise settle at what the row just
        proved it can absorb — bounded to [1, spec_tokens]."""
        k = int(self._spec_k[act.slot])
        if accepted >= offered:
            k = min(self.spec_tokens, k + 1)
        elif accepted == 0:
            k = max(1, k // 2)
        else:
            k = max(1, min(k, accepted + 1))
        self._spec_k[act.slot] = k
        self.spec_k_by_rid[act.req.rid] = k

    def _table_bucket(self, max_tokens: int) -> int:
        """Gather width (block-table columns) for this step: pow2-
        bucketed so compile count stays logarithmic while short batches
        never pay max_seq-width attention."""
        w = self.pool.table_width_for(max_tokens)
        return min(_next_pow2(w), self.pool.max_blocks_per_seq)

    def run(self, max_steps: Optional[int] = None) -> Dict[Any, np.ndarray]:
        """Drive until the queue and the batch drain; returns results
        (rid -> generated token ids)."""
        steps = 0
        while self.queue or self.active or self.prefilling:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self.stats.stop()
        return self.results

    def full_sequence(self, req: Request) -> np.ndarray:
        """Prompt + generated tokens for a completed request."""
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               self.results[req.rid]])
