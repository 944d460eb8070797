"""Multi-host serving mesh: sharded decode with a host-0 scheduler.

Training already runs on a device mesh; this module puts the SERVING
stack on one.  The existing :class:`repro.serve.session.DecodeSession`
/ :class:`repro.serve.kv_cache.PagedLayout` machinery is reused
unchanged — the mesh runtime only decides *where things live* and *who
decides*:

**Axis layout** (the "serve" preset,
:data:`repro.parallel.sharding.SERVE_RULES`):

  * **weights** — stationary, tensor-parallel over ``model`` (vocab /
    head / mlp / expert dims); never gathered, per-token collectives
    are tiny activation all-reduces;
  * **decode batch** — the ``num_slots`` rows split over ``data``:
    tokens, write indices, block tables, logits;
  * **cache leaves** — every one over ``data``: recurrent state on
    its batch dim, paged pools on the PAGE dim.  The paged pool
    becomes ``data``-many private sub-pools, each with its own null
    page, each accounted by a host-local
    :class:`repro.serve.kv_cache.PageShard`; block tables hold global
    page ids and the shard_map gather dispatch
    (:func:`repro.kernels.ops.paged_attention`) rebases them
    per-shard, so decode NEVER moves a KV page across ``data``.

**Control plane**: scheduling state (queue, slot maps, block
managers, prefix caches) is replicated host-side and evolves
deterministically — with two exceptions, both decided by **host 0**
and broadcast as a :class:`StepPlan` each step:

  * *admission* — which queued requests enter the batch this step
    (and implicitly which pinned pages get reclaimed for them);
  * *hot swap* — whether a newer tournament winner was found on disk
    (filesystem reads race the trainer; followers load exactly the
    broadcast step).

After the plan lands, every host executes the SAME jitted prefill /
decode dispatches on the sharded arrays.  Three plan transports
(:func:`make_plan_channel` picks one):

  * single process — the plan round-trips its wire encoding
    (:class:`LoopbackChannel`), so CI exercises the format every step;
  * multi-process on a collective-capable backend (TPU/GPU) — two
    ``multihost_utils.broadcast_one_to_all`` rounds
    (:class:`CollectiveChannel`);
  * multi-process on CPU — XLA's CPU backend cannot run cross-process
    computations, so the plan rides the **jax coordination service**
    (the gRPC key-value store ``jax.distributed.initialize`` already
    stood up): host 0 publishes the plan bytes under a per-step key,
    followers block on it with a timeout, and a per-step barrier both
    confirms delivery and turns a dead peer into a clean
    ``DEADLINE_EXCEEDED`` error instead of a hang
    (:class:`CoordServiceChannel`).
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch import specs as specs_lib
from repro.models import lm
from repro.parallel.sharding import (serve_rules, tree_shardings,
                                     use_sharding)
from repro.serve.kv_cache import PagedLayout, blocks_for
from repro.serve.scheduler import Request, Scheduler
from repro.serve.session import DecodeSession, _draft_unroll
from repro.serve.telemetry import stats_snapshot


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def parse_mesh(spec: str) -> Tuple[int, int]:
    """Parse ``--mesh`` values: "4,2" / "data=4,model=2" / "8" (pure
    data parallelism) -> (data, model)."""
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    named = {}
    sizes = []
    for p in parts:
        if "=" in p:
            k, v = p.split("=", 1)
            named[k.strip()] = int(v)
        else:
            sizes.append(int(p))
    if named:
        return named.get("data", 1), named.get("model", 1)
    if len(sizes) == 1:
        return sizes[0], 1
    if len(sizes) == 2:
        return sizes[0], sizes[1]
    raise ValueError(f"cannot parse mesh spec {spec!r}")


def make_serve_mesh(data: int, model: int = 1, local: bool = False):
    """("data", "model") mesh over the first data*model visible devices.

    ``local=True`` restricts the mesh to THIS process's devices
    (``jax.local_devices()``): the replicated-deployment mode
    ``launch/distributed.py`` uses on backends whose cross-process
    computations XLA does not support (CPU) — every process holds a
    full model replica on a private mesh and stays in lockstep through
    the broadcast plan instead of through device collectives.
    """
    from jax.sharding import Mesh
    n = data * model
    devices = jax.local_devices() if local else jax.devices()
    kind = "local" if local else "visible"
    if len(devices) < n:
        raise ValueError(
            f"serving mesh {data}x{model} needs {n} devices, have "
            f"{len(devices)} {kind} (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N to emulate)")
    return Mesh(np.asarray(devices[:n]).reshape(data, model),
                ("data", "model"))


def mesh_axis_sizes(mesh) -> Tuple[int, int]:
    """(data, model) axis sizes of a serving mesh (absent axes = 1)."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return axes.get("data", 1), axes.get("model", 1)


# ---------------------------------------------------------------------------
# the host-0 decision record
# ---------------------------------------------------------------------------


def encode_request(req: Request) -> Dict[str, Any]:
    """Wire-encode a :class:`~repro.serve.scheduler.Request` for the
    plan broadcast (JSON scalars + a token-id list; the rid must be a
    JSON scalar to be mesh-servable)."""
    return {"rid": req.rid,
            "prompt": [int(t) for t in req.prompt],
            "max_new": int(req.max_new),
            "eos_id": req.eos_id,
            "temperature": float(req.temperature),
            "seed": req.seed,
            "ttft_deadline_ms": req.ttft_deadline_ms,
            "tpot_deadline_ms": req.tpot_deadline_ms,
            "ntok_base": int(req.ntok_base)}


def decode_request(d: Dict[str, Any]) -> Request:
    """Inverse of :func:`encode_request` (follower-side)."""
    return Request(rid=d["rid"],
                   prompt=np.asarray(d["prompt"], np.int32),
                   max_new=d["max_new"], eos_id=d.get("eos_id"),
                   temperature=d.get("temperature", 0.0),
                   seed=d.get("seed"),
                   ttft_deadline_ms=d.get("ttft_deadline_ms"),
                   tpot_deadline_ms=d.get("tpot_deadline_ms"),
                   ntok_base=int(d.get("ntok_base", 0)))


@dataclass
class StepPlan:
    """One scheduler step's broadcastable decisions.

    ``winner`` — registry step of a newly found tournament winner
    (None: no swap this step); ``submits`` — wire-encoded requests
    that entered host 0's queue since the last step (followers enqueue
    them verbatim, which is how network-fed requests reach every
    host); ``cancels`` — ``[rid, reason]`` pairs applied before
    admission (client disconnects + deadline sheds — both clock-driven
    host-0 decisions); ``admits`` — rids admitted, in order; ``stop``
    — coordinated-shutdown marker (followers exit their replay loop).
    Everything else the schedulers do is a deterministic function of
    replicated state, so this is the WHOLE control-plane wire format.
    Request ids must be JSON scalars (int / str) to be mesh-servable.
    """
    winner: Optional[int] = None
    admits: List[Any] = field(default_factory=list)
    submits: List[Dict[str, Any]] = field(default_factory=list)
    cancels: List[List[Any]] = field(default_factory=list)
    stop: bool = False
    # online-LTFB arena: the member host 0's match evaluation promoted
    # to champion this step (None: no promotion).  Followers apply the
    # identical promotion before admission replay.
    promote: Optional[str] = None

    def encode(self) -> bytes:
        """Serialize to the JSON wire format (bytes)."""
        return json.dumps({"winner": self.winner,
                           "admits": list(self.admits),
                           "submits": list(self.submits),
                           "cancels": [list(c) for c in self.cancels],
                           "stop": self.stop,
                           "promote": self.promote}).encode()

    @classmethod
    def decode(cls, payload: bytes) -> "StepPlan":
        """Parse the JSON wire format (tolerates plans from older
        writers that lack the submit/cancel/stop/promote fields)."""
        d = json.loads(payload.decode())
        return cls(winner=d["winner"], admits=d["admits"],
                   submits=d.get("submits", []),
                   cancels=d.get("cancels", []),
                   stop=d.get("stop", False),
                   promote=d.get("promote"))


def broadcast_plan(plan: StepPlan) -> StepPlan:
    """Host-0 -> all-hosts broadcast of a step plan over DEVICE
    collectives.

    Multi-process: two ``broadcast_one_to_all`` rounds (length, then
    the padded byte buffer) — requires a backend whose cross-process
    computations XLA supports (TPU/GPU; the CPU backend does not, use
    :class:`CoordServiceChannel` there).  Single-process: the encode ->
    decode round trip still runs, so the wire format is exercised by
    every CI step, not just the multi-host deployment.
    """
    payload = plan.encode()
    if jax.process_count() > 1:  # pragma: no cover (single-process CI)
        from jax.experimental import multihost_utils
        n = int(multihost_utils.broadcast_one_to_all(
            np.int32(len(payload))))
        # followers contribute zeros: their local plan is discarded by
        # the broadcast, and its length need not match host 0's
        buf = np.zeros((n,), np.uint8)
        if jax.process_index() == 0:
            buf[:n] = np.frombuffer(payload, np.uint8)[:n]
        payload = multihost_utils.broadcast_one_to_all(buf).tobytes()
    return StepPlan.decode(payload)


# ---------------------------------------------------------------------------
# plan transports
# ---------------------------------------------------------------------------

# Distinguishes sequential channel lifetimes inside one process AND
# stays aligned across processes (every process constructs its
# schedulers in the same deterministic order).
_CHANNEL_SEQ = [0]


class PlanChannel:
    """Host-0 -> all-hosts transport for :class:`StepPlan` bytes.

    ``broadcast(plan)`` takes the decided plan on host 0 and ``None``
    on followers; every process receives the plan host 0 sent.  All
    transports round-trip the wire encoding, so host 0's returned plan
    is exactly what followers decode.  ``retries`` counts transient
    fetch retries a degradation-capable transport performed before
    succeeding (surfaced as the ``plan_retries`` serve counter).
    """

    retries: int = 0

    def broadcast(self, plan: Optional[StepPlan]) -> StepPlan:
        """Send (host 0) / receive (followers) one plan; blocking."""
        raise NotImplementedError

    def gather(self, payload: bytes) -> Optional[List[bytes]]:
        """All-ranks → host-0 gather of small stats payloads.

        Every rank calls this once per exchange with its own payload
        (symmetric, like a collective).  Host 0 receives the ordered
        list ``[rank0, rank1, …]``; followers receive ``None``.
        Transports that cannot aggregate return ``None`` everywhere —
        host 0's export then covers its own shard only.
        """
        return None

    def close(self) -> None:
        """Release transport resources (idempotent)."""


class LoopbackChannel(PlanChannel):
    """Single-process transport: the plan round-trips its wire
    encoding so the format is exercised on every step."""

    def broadcast(self, plan: Optional[StepPlan]) -> StepPlan:
        """Encode + decode the plan in-process (host 0 only)."""
        if plan is None:
            raise RuntimeError(
                "LoopbackChannel has no peer to receive from "
                "(follower replay passes the plan explicitly)")
        return StepPlan.decode(plan.encode())

    def gather(self, payload: bytes) -> Optional[List[bytes]]:
        """Single-process gather: host 0 is the only rank, so the
        aggregation path runs on every CI step with world size 1."""
        return [bytes(payload)]


class CollectiveChannel(PlanChannel):
    """Multi-process transport over device collectives
    (``multihost_utils.broadcast_one_to_all``) — TPU/GPU deployments
    where XLA runs cross-process computations."""

    def broadcast(self, plan: Optional[StepPlan]) -> StepPlan:
        """Two broadcast_one_to_all rounds; followers pass None."""
        return broadcast_plan(plan if plan is not None else StepPlan())

    def gather(self, payload: bytes) -> Optional[List[bytes]]:
        """All-gather fixed-width padded payloads over the device
        collective; host 0 strips the padding per rank."""
        if jax.process_count() == 1:
            return [bytes(payload)]
        from jax.experimental import multihost_utils  # pragma: no cover
        buf = np.frombuffer(payload, np.uint8)  # pragma: no cover
        lens = multihost_utils.process_allgather(  # pragma: no cover
            np.int32(len(buf)))
        width = int(lens.max())  # pragma: no cover
        pad = np.zeros((width,), np.uint8)  # pragma: no cover
        pad[:len(buf)] = buf  # pragma: no cover
        allp = multihost_utils.process_allgather(pad)  # pragma: no cover
        if jax.process_index() != 0:  # pragma: no cover
            return None
        return [allp[r, :int(lens[r])].tobytes()  # pragma: no cover
                for r in range(allp.shape[0])]


def _capture(fn, *args):
    """Run ``fn`` and box the outcome (worker-thread helper for
    :meth:`CoordServiceChannel._deadlined`)."""
    try:
        return ("ok", fn(*args))
    except Exception as e:  # noqa: BLE001 — re-raised by the caller
        return ("err", e)


class CoordServiceChannel(PlanChannel):
    """Multi-process transport over the jax coordination service.

    The gRPC key-value store ``jax.distributed.initialize`` stands up
    is host-side — no device hop, and it works on the CPU backend
    where XLA's cross-process computations do not.  Per step ``n``:
    host 0 ``key_value_set_bytes(<ns>/<n>, plan)``, followers
    ``blocking_key_value_get_bytes`` it with ``timeout_s``, then all
    processes meet at barrier ``<ns>/b<n>`` (same timeout), after
    which host 0 deletes the key — the store holds at most one
    in-flight plan.  A dead peer turns into ``DEADLINE_EXCEEDED``
    at the barrier/get instead of an indefinite hang; we re-raise it
    as a RuntimeError naming the step and timeout.

    **Degradation**: the blocking KV *fetches* (follower plan get,
    host-0 stats gather) are retried ``max_retries`` times with
    exponential backoff before the peer is declared dead — a host
    paused by a GC stall or a slow NFS poll gets another chance; the
    retry count is surfaced as ``plan_retries``.  The delivery
    *barrier* is NOT retried: barrier state on the coordination
    service is not safely re-enterable after a timeout, so a barrier
    deadline is treated as confirmed peer death immediately.
    """

    def __init__(self, timeout_s: float = 60.0,
                 namespace: Optional[str] = None,
                 max_retries: int = 2, backoff_s: float = 0.05):
        from jax._src import distributed
        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "CoordServiceChannel needs jax.distributed.initialize() "
                "(no coordination-service client in this process)")
        self._client = client
        # rank from the coordination client, NOT jax.process_index():
        # the latter lazily initializes the device backend, whose
        # multi-process topology exchange hangs if a peer is already
        # dead — exactly when this channel must raise, not hang
        self._rank = int(distributed.global_state.process_id or 0)
        self._world = int(distributed.global_state.num_processes or 1)
        self._timeout_ms = max(1, int(timeout_s * 1000))
        if namespace is None:
            namespace = f"repro/plan{_CHANNEL_SEQ[0]}"
            _CHANNEL_SEQ[0] += 1
        self._ns = namespace
        self._seq = 0
        self._gseq = 0
        self._max_retries = max(0, int(max_retries))
        self._backoff_s = float(backoff_s)
        self.retries = 0

    def _deadlined(self, fn, *args):
        """Run a blocking coordination-service call with a HARD
        client-side deadline.

        The service's own timeouts are not sufficient: a peer that
        exits through Python's atexit (jax's distributed-shutdown
        handshake) leaves its connection half-closed, and
        ``wait_at_barrier`` has been observed to block far past its
        deadline in that state.  The call runs on a daemon thread and
        we abandon it after ``timeout + 5s`` — the thread leaks, but
        the caller is about to tear the process down anyway.
        """
        import queue as queue_mod
        import threading
        out: "queue_mod.Queue" = queue_mod.Queue()
        t = threading.Thread(
            target=lambda: out.put(_capture(fn, *args)), daemon=True)
        t.start()
        try:
            kind, val = out.get(timeout=self._timeout_ms / 1000 + 5.0)
        except queue_mod.Empty:
            raise TimeoutError(
                f"coordination-service call did not return within "
                f"{self._timeout_ms} ms (+5 s grace)") from None
        if kind == "err":
            raise val
        return val

    def _get_with_retry(self, key: str) -> bytes:
        """Blocking KV fetch with bounded retry + exponential backoff
        (the mesh-degradation knob: a slow peer is retried before
        being declared dead; each retry counts into ``retries``)."""
        delay = self._backoff_s
        for attempt in range(self._max_retries + 1):
            try:
                return self._deadlined(
                    self._client.blocking_key_value_get_bytes,
                    key, self._timeout_ms)
            except Exception:
                if attempt >= self._max_retries:
                    raise
                self.retries += 1
                print(f"[mesh] fetch of {key!r} timed out; retry "
                      f"{attempt + 1}/{self._max_retries} in {delay:.2f}s",
                      flush=True)
                time.sleep(delay)
                delay *= 2

    def broadcast(self, plan: Optional[StepPlan]) -> StepPlan:
        """One KV publish/fetch + delivery barrier; blocking with the
        channel's timeout (fetches retried per the channel's
        degradation policy).  Raises RuntimeError on confirmed peer
        death."""
        key = f"{self._ns}/{self._seq}"
        try:
            if self._rank == 0:
                if plan is None:
                    plan = StepPlan()
                self._client.key_value_set_bytes(key, plan.encode())
                payload = plan.encode()
            else:
                payload = self._get_with_retry(key)
            self._deadlined(self._client.wait_at_barrier,
                            f"{self._ns}/b{self._seq}", self._timeout_ms)
        except Exception as e:  # DEADLINE_EXCEEDED / TimeoutError
            raise RuntimeError(
                f"plan broadcast for step {self._seq} timed out after "
                f"{self._timeout_ms} ms — a peer process likely died "
                f"({type(e).__name__}: {e})") from e
        if self._rank == 0:
            self._client.key_value_delete(key)
        self._seq += 1
        return StepPlan.decode(payload)

    def gather(self, payload: bytes) -> Optional[List[bytes]]:
        """Followers publish their payload under a per-exchange key;
        host 0 blocking-gets every rank's (with the channel's hard
        deadline) and deletes the keys.  No barrier needed: the
        blocking gets ARE the synchronization, and the next plan
        broadcast's barrier keeps steps aligned."""
        seq = self._gseq
        self._gseq += 1
        try:
            if self._rank != 0:
                self._client.key_value_set_bytes(
                    f"{self._ns}/stats{seq}/{self._rank}", payload)
                return None
            out = [bytes(payload)]
            for r in range(1, self._world):
                out.append(self._get_with_retry(
                    f"{self._ns}/stats{seq}/{r}"))
            for r in range(1, self._world):
                self._client.key_value_delete(f"{self._ns}/stats{seq}/{r}")
            return out
        except Exception as e:  # DEADLINE_EXCEEDED / TimeoutError
            raise RuntimeError(
                f"stats gather {seq} timed out after {self._timeout_ms} "
                f"ms — a peer process likely died "
                f"({type(e).__name__}: {e})") from e


def make_plan_channel(timeout_s: float = 60.0) -> PlanChannel:
    """Pick the plan transport for this process topology: loopback
    single-process, device collectives where XLA supports them
    cross-process (TPU/GPU), the coordination service on CPU."""
    if jax.process_count() == 1:
        return LoopbackChannel()
    if jax.default_backend() in ("gpu", "tpu"):  # pragma: no cover
        return CollectiveChannel()
    return CoordServiceChannel(timeout_s=timeout_s)


# ---------------------------------------------------------------------------
# sharded decode session
# ---------------------------------------------------------------------------

# The serving-mesh rule set the mesh jits trace under.  Two overrides
# on the preset: a one-shot prefill's K/V shards its HEADS over
# `model`, as the page pools it is scattered into do, not its
# sequence dim; and recurrent STATE rows stay whole per slot
# (splitting the state contraction over `model` would reorder f32
# accumulation and cost mesh-vs-single-device token identity for
# hybrid/ssm stacks).
MESH_SERVE_RULES = serve_rules(kv_seq=None, state=None)


# Mesh-DEDICATED jitted entry points, with the Mesh itself a STATIC
# argument.  This is load-bearing, not a convenience: jax caches traced
# jaxprs by aval (sharding excluded), so if the mesh path shared the
# single-device session's jits, whichever traced first would bake its
# trace-time decisions — `constrain` targets and the shard_map
# paged-gather dispatch — into the other's lowering.  A separate
# function object keyed on the mesh guarantees every mesh trace happens
# inside the mesh's sharding context, and two meshes never alias.


@partial(jax.jit, static_argnums=(1, 2), donate_argnums=(4,))
def _mesh_step_tables_fn(params, cfg, mesh, tokens, cache, index,
                         tables, valid):
    with use_sharding(mesh, **MESH_SERVE_RULES):
        return lm.lm_decode(params, cfg, tokens, cache, index,
                            tables=tables, valid=valid)


@partial(jax.jit, static_argnums=(1, 2))
def _mesh_prefill_fn(params, cfg, mesh, toks, last_pos):
    with use_sharding(mesh, **MESH_SERVE_RULES):
        return lm.lm_prefill(params, cfg, {"tokens": toks},
                             last_pos=last_pos)


@partial(jax.jit, static_argnums=(1, 2), donate_argnums=(4,))
def _mesh_chunk_fn(params, cfg, mesh, toks, cache, tables, hist, plen,
                   last_pos):
    with use_sharding(mesh, **MESH_SERVE_RULES):
        return lm.lm_prefill(params, cfg, {"tokens": toks},
                             last_pos=last_pos, cache=cache,
                             tables=tables, hist_len=hist,
                             prompt_len=plen)


@partial(jax.jit, static_argnums=(1, 2, 7), donate_argnums=(4,))
def _mesh_draft_tables_fn(params, cfg, mesh, tok0, cache, index, valid,
                          steps, tables):
    with use_sharding(mesh, **MESH_SERVE_RULES):
        return _draft_unroll(params, cfg, tok0, cache, index, valid,
                             steps, tables)


class MeshDecodeSession(DecodeSession):
    """A DecodeSession whose model calls trace under the serving mesh.

    All host-side marshalling is inherited; only the jit-indirection
    hooks are overridden to bind the mesh-dedicated jits above (the
    Mesh injected as their static argument), so every trace runs
    inside :func:`use_sharding`: ``constrain`` calls in the model
    resolve against the mesh (activations stay ``data``-sharded) and
    the paged-gather dispatch in ``kernels/ops.py`` lowers to its
    shard_map form.  ``params`` is the RAW (host / single-device)
    tree; the session places it — and re-places on ``set_params`` hot
    swaps, skipping the transfer when handed the same object.
    """

    def __init__(self, cfg: ModelConfig, params, layout, mesh, rules,
                 placer):
        super().__init__(cfg, params, layout)
        self.mesh = mesh
        self.rules = rules
        self._place = placer
        self._src_params = None
        self.set_params(params)

    def set_params(self, params) -> None:
        """Hot-swap weights, re-placing them onto the mesh (no-op when
        the same pytree is already installed)."""
        if params is self._src_params:
            return
        self._src_params = params
        self.params = self._place(params)

    # -- jit indirection: mesh-dedicated executables -------------------------
    def _call_prefill(self, params, cfg, *args):
        return _mesh_prefill_fn(params, cfg, self.mesh, *args)

    def _call_chunk(self, params, cfg, *args):
        return _mesh_chunk_fn(params, cfg, self.mesh, *args)

    def _call_step_tables(self, params, cfg, *args):
        return _mesh_step_tables_fn(params, cfg, self.mesh, *args)

    def _call_draft_tables(self, params, cfg, *args):
        return _mesh_draft_tables_fn(params, cfg, self.mesh, *args)

    def step(self, tokens: np.ndarray, index: np.ndarray,
             valid: Optional[np.ndarray] = None,
             width: Optional[int] = None,
             rows: Optional[np.ndarray] = None,
             tables: Optional[np.ndarray] = None) -> jax.Array:
        """One full-batch decode step on the mesh (row subsets are a
        single-host optimization the sharded path rejects)."""
        if rows is not None or tables is not None:
            raise ValueError(
                "row-subset / explicit-table steps cannot run on the "
                "mesh (rows must stay in their data shard)")
        return super().step(tokens, index, valid=valid, width=width)


def cache_placer(mesh, rules):
    """(cache, axes) -> device-placed cache, under the serve rules —
    the ONE placement implementation every mesh session/layout uses."""
    def place(cache, axes):
        return jax.device_put(
            cache, tree_shardings(mesh, axes, cache, **rules))
    return place


def param_placer(mesh, rules, cfg: ModelConfig):
    """params -> device-placed params for ``cfg``.  The logical axes
    come from one eval_shape at closure build time, so hot swaps
    re-place with cached axes."""
    _, axes = specs_lib.param_specs(cfg)

    def place(params):
        return jax.device_put(
            params, tree_shardings(mesh, axes, params, **rules))
    return place


# ---------------------------------------------------------------------------
# the mesh scheduler
# ---------------------------------------------------------------------------


class MeshScheduler(Scheduler):
    """Continuous-batching scheduler over a ("data", "model") mesh.

    All scheduling semantics are inherited — admission by token
    budget, chunked prefill, prefix sharing/pinning per shard,
    drain-aware hot swap, speculative decoding — with three mesh
    specifics:

    * **geometry** — ``num_slots`` / ``num_blocks`` are rounded up to
      multiples of the ``data`` axis; each request's pages live wholly
      in its slot's shard, so the per-request cap is the SHARD's
      capacity, not the pool's;
    * **decisions** — :meth:`step` produces a :class:`StepPlan` on
      host 0 and routes it through :func:`broadcast_plan`; a follower
      replica replays with ``step(plan=...)`` and must land in an
      identical state (asserted, and tested);
    * **dispatch** — every session is a :class:`MeshDecodeSession`;
      the ragged width-split subset dispatch is disabled (a subset of
      rows cannot be re-sharded over ``data`` without breaking the
      slot <-> shard alignment).
    """

    def __init__(self, cfg: ModelConfig, params, *, mesh=None,
                 mesh_shape: Optional[Tuple[int, int]] = None,
                 channel: Optional[PlanChannel] = None,
                 local_mesh: bool = False,
                 step_timeout_s: float = 60.0,
                 stats_every: int = 1, **kwargs):
        if cfg.mla is not None:
            raise ValueError("the serving mesh holds no latent (MLA) pool "
                             "yet: serve latent attention on one device")
        if mesh is None:
            if mesh_shape is None:
                mesh_shape = (jax.device_count(), 1)
            mesh = make_serve_mesh(*mesh_shape, local=local_mesh)
        self.mesh = mesh
        self.channel = channel if channel is not None \
            else make_plan_channel(timeout_s=step_timeout_s)
        # every N steps each rank ships a stats snapshot to host 0 over
        # the channel's gather (0 disables the exchange entirely);
        # MUST be identical on every rank — the exchange is symmetric
        self.stats_every = max(0, int(stats_every))
        # host-0 decisions pending broadcast in the next step's plan
        self._pending_submits: List[Dict[str, Any]] = []
        self._pending_cancels: List[Tuple[Any, str]] = []
        self.data_shards, self.model_shards = mesh_axis_sizes(mesh)
        self.rules = MESH_SERVE_RULES
        D = self.data_shards
        num_slots = kwargs.get("num_slots", 8)
        kwargs["num_slots"] = -(-num_slots // D) * D
        max_len = kwargs.get("max_len", 1024)
        block_size = kwargs.get("block_size", 16)
        n_blocks = kwargs.get("num_blocks")
        if n_blocks is None:
            n_blocks = kwargs["num_slots"] * blocks_for(max_len,
                                                        block_size)
        kwargs["num_blocks"] = -(-n_blocks // D) * D
        super().__init__(cfg, params, **kwargs)
        # subset dispatch cannot keep rows in their shard's partition
        self._group_decode = False

    # -- construction hooks --------------------------------------------------
    def _make_layout(self, cfg: ModelConfig) -> PagedLayout:
        g = self._geom
        return PagedLayout(cfg, g["num_slots"], g["n_blocks"],
                           block_size=g["block_size"],
                           max_seq=g["max_seq"],
                           pin_prefix=g["pin_prefix"],
                           data_shards=self.data_shards,
                           placer=cache_placer(self.mesh, self.rules))

    def _make_session(self, cfg: ModelConfig, params,
                      layout) -> DecodeSession:
        return MeshDecodeSession(
            cfg, params, layout, self.mesh, self.rules,
            param_placer(self.mesh, self.rules, cfg))

    # -- admission (shard-aligned drafter) -----------------------------------
    def _can_admit_head(self) -> bool:
        if self.draft is None or self.data_shards == 1:
            return super()._can_admit_head()
        req = self.queue[0]
        total = req.prompt_len + req.max_new
        if not self._pool_can_admit(self.pool, total, head=True):
            return False
        head = self._head_share
        shared = head[1][0] if head is not None and head[0] == req.rid \
            else ()
        shard = self.pool.peek_shard(total, shared)
        if shard is None:
            return False
        # the drafter's mirror admit lands at the SAME slot, hence the
        # same shard — its capacity must hold there, not just anywhere
        return self.draft.layout.shards[shard].blocks.can_allocate(total)

    # -- host-0 intake (recorded for the next plan broadcast) ----------------
    def submit(self, req: Request) -> None:
        """Enqueue a request AND record its wire encoding for the next
        plan broadcast, so followers that never saw the network request
        (gateway ingress lands on host 0 only) enqueue an identical
        copy before replaying the admission decisions."""
        super().submit(req)
        self._pending_submits.append(encode_request(req))

    def cancel(self, rid) -> bool:
        """Request cancellation of ``rid`` (host 0 only).

        Deferred to the next :meth:`step` so the drop happens at the
        same point of the step on every host (broadcast in the plan's
        ``cancels``).  Returns True if the rid is currently live; the
        cancel is a no-op if the request finishes first.
        """
        if rid not in self.active and rid not in self.prefilling and \
                not any(q.rid == rid for q in self.queue):
            return False
        self._pending_cancels.append((rid, "cancel"))
        return True

    def shed_expired(self) -> List[Any]:
        """Host-0 TTFT-deadline shedding: the clock is read HERE only;
        the victims ride the next plan's ``cancels`` so followers drop
        exactly the same queued requests.  Returns the rids shed."""
        now = time.perf_counter()
        pending = {rid for rid, _ in self._pending_cancels}
        shed = [q.rid for q in self.queue
                if q.rid not in pending
                and q.ttft_deadline_ms is not None
                and (now - getattr(q, "_submit_t", now)) * 1e3
                > q.ttft_deadline_ms]
        self._pending_cancels.extend((rid, "deadline") for rid in shed)
        return shed

    # -- host-0 plan / broadcast / replay ------------------------------------
    def step(self, plan: Optional[StepPlan] = None) -> StepPlan:
        """One scheduler iteration.

        ``plan=None`` on host 0: poll + decide + broadcast (the plan
        ALWAYS round-trips its wire encoding, single-process included).
        ``plan=None`` on a follower process: receive host 0's plan from
        the channel (blocking, with the channel's timeout).
        ``plan=...``: the explicit replay path — apply host 0's
        decisions verbatim, then run the identical jitted phases.
        Returns the plan that was executed; ``plan.stop`` means host 0
        initiated shutdown and no phases ran.
        """
        self.stats.start()
        self.telemetry.step_begin(self._step_count + 1)
        if self.faults is not None:
            self.faults.on_step(self, self._step_count + 1)
        if plan is None and jax.process_index() == 0:
            winner = self._poll_registry()
            self._step_count += 1
            self._apply_swap(winner)
            self._arena_rotate()
            promote = self._arena_decide()
            self._arena_apply(promote)
            submits = list(self._pending_submits)
            self._pending_submits.clear()
            cancels = [[rid, reason] for rid, reason
                       in self._pending_cancels
                       if self._cancel_now(rid, reason)]
            self._pending_cancels.clear()
            with self.telemetry.span("admit") as sp:
                admits = self._admission_phase()
                sp.emit = bool(admits)
            plan = self.channel.broadcast(StepPlan(
                winner=winner, admits=admits, submits=submits,
                cancels=cancels, promote=promote))
        else:
            if plan is None:  # pragma: no cover (multi-host follower)
                plan = self.channel.broadcast(None)
            if plan.stop:
                # balance step_begin (closes an armed profiler window)
                self.telemetry.step_end()
                return plan
            self._step_count += 1
            if plan.winner is not None and self.registry is not None:
                self.registry.load_step(plan.winner)
                self._apply_swap(plan.winner)
            else:
                # no registry attached: there is nothing to swap to —
                # but still run the pending-drain half of the check
                self._apply_swap(None)
            # arena: followers replay host 0's promotion verbatim (the
            # rotation itself is a pure function of replicated state)
            self._arena_rotate()
            self._arena_apply(plan.promote)
            self._apply_submits(plan.submits)
            for rid, reason in plan.cancels:
                self._cancel_now(rid, reason)
            self._replay_admissions(plan.admits)
        self._device_phases()
        self._exchange_stats()
        self.stats.sample_step(len(self.queue),
                               len(self.active) + len(self.prefilling))
        self.stats.plan_retries = getattr(self.channel, "retries", 0)
        self._journal_step()
        self.telemetry.step_end()
        return plan

    def _exchange_stats(self) -> None:
        """Symmetric per-step stats exchange: every rank ships its
        :func:`repro.serve.telemetry.stats_snapshot` to host 0 through
        the channel's ``gather``; host 0 keeps the latest snapshot per
        rank in ``remote_stats`` (what ``GET /metrics`` and the
        distributed launcher export).  Runs every ``stats_every`` steps
        on ALL ranks or none — the gather is a collective."""
        if not self.stats_every or self._step_count % self.stats_every:
            return
        rank = jax.process_index()
        snap = stats_snapshot(self, rank=rank)
        got = self.channel.gather(json.dumps(snap).encode())
        if got is not None:
            snaps = [json.loads(p.decode()) for p in got]
            self.remote_stats = {int(s["rank"]): s for s in snaps}

    def shutdown(self) -> StepPlan:
        """Host 0: broadcast the coordinated-shutdown plan and close
        the channel.  Followers return from :meth:`step` (or
        :meth:`run_follower`) when they receive it, so every process
        exits its serve loop on the same step."""
        plan = self.channel.broadcast(StepPlan(stop=True))
        self.stats.stop()
        self.channel.close()
        return plan

    def run_follower(self) -> Dict[Any, np.ndarray]:
        """Follower serve loop: replay broadcast plans until host 0's
        stop plan arrives (or the channel times out — a dead host 0
        raises instead of hanging).  Returns the replica's results,
        which mirror host 0's exactly."""
        while True:
            plan = self.step()
            if plan.stop:
                break
        self.stats.stop()
        self.channel.close()
        return self.results

    def _apply_submits(self, submits: List[Dict[str, Any]]) -> None:
        """Enqueue host 0's newly submitted requests on a follower.

        Requests this process already holds (replicated feeds, or the
        host-0 replica itself replaying its own plan in tests) are
        recognized by rid and skipped — the wire copy and the local
        copy are identical by construction.
        """
        self._pending_submits.clear()
        known = {q.rid for q in self.queue}
        # host 0 already ruled on overload at ingress time; replaying
        # the max_queue check here (against the batched queue depth)
        # could diverge, so it is suspended for the replay
        saved, self.max_queue = self.max_queue, None
        try:
            for d in submits:
                rid = d["rid"]
                if rid in known or rid in self.active \
                        or rid in self.prefilling or rid in self.results:
                    continue
                Scheduler.submit(self, decode_request(d))
        finally:
            self.max_queue = saved

    def _replay_admissions(self, admits: List[Any]) -> None:
        """Apply host 0's admission decisions on a follower: the local
        queue must agree (requests are submitted identically on every
        host), and local accounting must accept each admission — any
        divergence is a hard error, not a silent drift."""
        for rid in admits:
            if not self.queue or self.queue[0].rid != rid:
                raise RuntimeError(
                    f"follower queue diverged from host 0: expected "
                    f"{rid!r} at the head, have "
                    f"{self.queue[0].rid if self.queue else None!r}")
            if not self._can_admit_head():
                raise RuntimeError(
                    f"follower cannot admit {rid!r}: scheduler state "
                    "diverged from host 0")
            self._admit(self.queue.popleft())
