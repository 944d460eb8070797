"""Batched ICF-surrogate serving (the paper's actual end product).

The trained CycleGAN surrogate answers "what does the experiment
produce for inputs x?" queries — `x (5,) -> output bundle (15 scalars +
12 images)` via :func:`repro.models.icf_cyclegan.predict`.  Queries of
any size are micro-batched: the queue is drained up to ``max_batch``
rows per step and padded to a bucket so the jitted forward compiles for
a bounded set of shapes.  A :class:`repro.serve.registry.ModelRegistry`
can be attached for the same between-steps winner hot-swap the LM
scheduler does.

**Host/device overlap** — the same double-buffering the datastore's
:class:`repro.datastore.store.PrefetchLoader` applies to training
batches, in software-pipeline form: each ``step`` (1) dispatches the
device forward for the batch staged on the previous step (JAX dispatch
is async), (2) stages the NEXT micro-batch — drain, concatenate, pad —
while the device is busy, and only then (3) blocks on the in-flight
result and distributes it.  Host staging therefore costs zero
wall-clock whenever the device compute is longer, instead of
serializing with it as it did pre-paged-attention.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.icf_cyclegan import CycleGANConfig
from repro.models import icf_cyclegan as cg
from repro.serve.metrics import ServeStats
from repro.serve.telemetry import ServeTelemetry

# a staged micro-batch: (taken queue items, true rows, padded array)
_Staged = Tuple[List[Tuple[Any, np.ndarray, float]], int, np.ndarray]


class SurrogateEngine:
    """Micro-batching front end over the jitted surrogate forward."""

    def __init__(self, cfg: CycleGANConfig, params, max_batch: int = 64,
                 bucket: int = 8, registry=None, watch_every: int = 0,
                 telemetry: bool = True):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.bucket = bucket
        self.registry = registry
        self.watch_every = watch_every
        self._forward = jax.jit(lambda p, x: cg.predict(p["gen"], x))
        self.queue: deque[Tuple[Any, np.ndarray, float]] = deque()
        self.results: Dict[Any, np.ndarray] = {}
        self.stats = ServeStats(slots=max_batch)
        self.telemetry = ServeTelemetry(enabled=telemetry)
        self._step_count = 0
        # software pipeline state: the batch staged for the next
        # dispatch, and the batch whose device compute is in flight
        self._staged: Optional[_Staged] = None
        self._pending: Optional[Tuple[List, int, int, jax.Array]] = None
        self.overlapped_stages = 0

    def submit(self, rid: Any, x: np.ndarray) -> None:
        """x: (n, input_dim) float batch of experiment-parameter rows."""
        x = np.atleast_2d(np.asarray(x, np.float32))
        if x.shape[1] != self.cfg.input_dim:
            self.stats.rejected += 1
            raise ValueError(
                f"query {rid!r}: expected (n, {self.cfg.input_dim}), "
                f"got {x.shape}")
        self.stats.submitted += 1
        t0 = time.perf_counter()
        self.queue.append((rid, x, t0))
        self.telemetry.req_instant(rid, "enqueue", t=t0,
                                   rows=int(x.shape[0]))

    def _pad(self, n: int) -> int:
        b = self.bucket
        return ((n + b - 1) // b) * b

    def _stage(self) -> Optional[_Staged]:
        """Drain up to max_batch rows off the queue and assemble the
        padded host array (the host work the pipeline overlaps)."""
        taken, rows = [], 0
        while self.queue and rows + self.queue[0][1].shape[0] \
                <= self.max_batch:
            item = self.queue.popleft()
            taken.append(item)
            rows += item[1].shape[0]
        if not taken and self.queue:
            # head query alone exceeds max_batch: serve it as its own
            # (oversized) micro-batch rather than stalling the queue
            item = self.queue.popleft()
            taken.append(item)
            rows = item[1].shape[0]
        if not taken:
            return None
        x = np.concatenate([t[1] for t in taken])
        padded = self._pad(rows)
        if padded > rows:
            x = np.concatenate([x, np.zeros((padded - rows, x.shape[1]),
                                            np.float32)])
        return taken, rows, x

    def _dispatch(self, staged: _Staged) -> None:
        taken, rows, x = staged
        y = self._forward(self.params, jnp.asarray(x))   # async dispatch
        self._pending = (taken, rows, x.shape[0], y)

    def _collect(self) -> None:
        """Block on the in-flight forward and distribute its results."""
        taken, rows, padded, y = self._pending
        self._pending = None
        with self.telemetry.span("surrogate_collect", rows=rows):
            y = np.asarray(y.astype(jnp.float32))
        now = time.perf_counter()
        off = 0
        for rid, q, t0 in taken:
            n = q.shape[0]
            self.results[rid] = y[off:off + n]
            off += n
            self.stats.completed += 1
            self.stats.ttft.append(now - t0)
            self.stats.latency.append(now - t0)
            self.telemetry.terminal(rid, "finish", t=now,
                                    latency_s=now - t0, rows=n)
        self.stats.prefills += 1
        self.stats.prefill_tokens += rows       # true query rows
        self.stats.padded_prefill_tokens += padded
        self.stats.decode_steps += 1
        self.stats.decode_tokens += rows
        self.stats.decode_slot_steps += padded
        self.stats.sample_step(len(self.queue), rows)

    def step(self) -> None:
        """One pipeline step: dispatch the staged batch, stage the next
        one while the device computes, then collect."""
        self.stats.start()
        self._step_count += 1
        if (self.registry is not None and self.watch_every > 0
                and self._step_count % self.watch_every == 0
                and self.registry.refresh()):
            self.params = self.registry.params
            self.stats.hot_swaps += 1
        staged = self._staged if self._staged is not None else self._stage()
        self._staged = None
        if staged is not None:
            self._dispatch(staged)
        # overlap: assemble the NEXT micro-batch while the device is
        # busy with the one just dispatched
        self._staged = self._stage()
        if self._pending is not None:
            if self._staged is not None:
                self.overlapped_stages += 1
            self._collect()
        else:
            self.stats.sample_step(len(self.queue), 0)

    def run(self, max_steps: Optional[int] = None) -> Dict[Any, np.ndarray]:
        """Drain the query queue (optionally bounded); returns results
        keyed by query id."""
        steps = 0
        while self.queue or self._staged is not None \
                or self._pending is not None:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        if self._pending is not None:    # flush the in-flight batch
            self._collect()
        self.stats.stop()
        return self.results
