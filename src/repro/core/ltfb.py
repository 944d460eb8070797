"""LTFB — "Let a Thousand Flowers Bloom" tournament training (paper §III-C).

Two faithful realizations of the paper's algorithm:

1. **Mesh-native** (:func:`tournament_step`, :func:`make_ltfb_step`) — the
   trainer population lives on a dedicated ``trainer`` mesh axis; model
   exchange is ``jax.lax.ppermute`` (HLO ``collective-permute``, the exact
   peer-to-peer pattern of the paper's MPI sendrecv), and tournament
   evaluation + winner selection compile into the same XLA program as
   training.  Pairings use a *butterfly (hypercube) schedule*: round r
   pairs trainer i with i XOR 2^(r mod log2 K).  This is the TPU-native
   adaptation of the paper's random pairing (DESIGN.md §2): every pairing
   is a static collective-permute (no retracing), and after log2 K rounds
   information has provably mixed across the whole population — the same
   "encoded propagation of data partitions" effect.

2. **Host-orchestrated** (:mod:`repro.core.population`) — the paper's
   random pairing with an explicit population, used by the benchmark
   experiments (Figs. 11–13) and for fault-tolerant/elastic deployments.

Both keep the discriminator local and exchange only the generator for
GANs (``exchange_scope``), per the paper's GAN extension.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Any
MetricFn = Callable[[Params, Dict[str, jax.Array]], jax.Array]


# ---------------------------------------------------------------------------
# Pairing schedules
# ---------------------------------------------------------------------------


def random_pairing(num_trainers: int, round_idx: int, seed: int = 0,
                   alive: Optional[Sequence[bool]] = None) -> np.ndarray:
    """Paper pairing: random disjoint pairs each round.

    Returns ``partner[i]`` (an involution).  Trainers that are down
    (``alive[i] == False``) or the odd one out self-pair — this is the
    straggler/failure mitigation: a missing partner never blocks a round.
    """
    rng = np.random.default_rng(hash((seed, round_idx)) % (2 ** 63))
    partner = np.arange(num_trainers)
    idx = [i for i in range(num_trainers)
           if alive is None or alive[i]]
    rng.shuffle(idx)
    for a, b in zip(idx[::2], idx[1::2]):
        partner[a], partner[b] = b, a
    return partner


def butterfly_pairing(num_trainers: int, round_idx: int) -> np.ndarray:
    """Hypercube schedule: i <-> i XOR 2^(r mod log2 K). Static involution."""
    assert num_trainers & (num_trainers - 1) == 0, "power-of-two trainers"
    bit = 1 << (round_idx % max(1, num_trainers.bit_length() - 1))
    return np.arange(num_trainers) ^ bit


def pairing_to_perm(partner: np.ndarray) -> List[Tuple[int, int]]:
    """ppermute (source, destination) pairs for a partner involution."""
    return [(int(i), int(partner[i])) for i in range(len(partner))]


# ---------------------------------------------------------------------------
# Exchange scope (GAN: generator only)
# ---------------------------------------------------------------------------


def split_scope(params: Params, scope: str) -> Tuple[Params, Params]:
    """Split params into (exchanged, local) per the exchange scope."""
    if scope == "full":
        return params, None
    if scope == "generator":
        local = {k: v for k, v in params.items() if k != "gen"}
        return params["gen"], local
    raise ValueError(scope)


def merge_scope(exchanged: Params, local: Params, scope: str) -> Params:
    if scope == "full":
        return exchanged
    return {**local, "gen": exchanged}


# ---------------------------------------------------------------------------
# Mesh-native tournament step
# ---------------------------------------------------------------------------


def tournament_shard(params: Params, batch: Dict[str, jax.Array],
                     metric_fn: MetricFn, perm: List[Tuple[int, int]],
                     axis: str = "trainer", scope: str = "full",
                     quantize: bool = False):
    """Body executed *inside* shard_map over the trainer axis.

    params/batch are the local (per-trainer) shard.  Returns the winner's
    params (and the local/received metrics for logging).

    ``quantize=True`` (beyond-paper): the exchanged model crosses the
    wire as int8 + per-tensor scales (4x less collective-permute volume
    than f32, 2x less than bf16).  The receiving trainer evaluates and —
    if adopted — continues training from the dequantized weights; GAN
    tournament selection is robust to the quantization (validated in
    tests/test_ltfb.py).
    """
    from repro.optim.compression import dequantize_int8, quantize_int8

    exch, local = split_scope(params, scope)
    if quantize:
        q_and_s = jax.tree.map(quantize_int8, exch)
        qs = jax.tree.map(lambda t: t[0], q_and_s,
                          is_leaf=lambda t: isinstance(t, tuple)
                          and len(t) == 2 and hasattr(t[0], "dtype"))
        ss = jax.tree.map(lambda t: t[1], q_and_s,
                          is_leaf=lambda t: isinstance(t, tuple)
                          and len(t) == 2 and hasattr(t[0], "dtype"))
        q_r = jax.lax.ppermute(qs, axis, perm)
        s_r = jax.lax.ppermute(ss, axis, perm)
        received = jax.tree.map(
            lambda q, s, like: dequantize_int8(q, s).astype(like.dtype),
            q_r, s_r, exch)
    else:
        received = jax.lax.ppermute(exch, axis, perm)
    cand_local = params
    cand_other = merge_scope(received, local, scope)
    m_local = metric_fn(cand_local, batch)
    m_other = metric_fn(cand_other, batch)
    take_other = m_other < m_local
    new_params = jax.tree.map(
        lambda a, b: jnp.where(take_other, b, a), cand_local, cand_other)
    return new_params, m_local, m_other


def _squeeze0(tree):
    return jax.tree.map(lambda x: x[0] if x.ndim else x, tree)


def _unsqueeze0(tree):
    return jax.tree.map(lambda x: x[None], tree)


def make_ltfb_step(metric_fn: MetricFn, num_trainers: int,
                   mesh, axis: str = "trainer", scope: str = "full",
                   param_specs=None, batch_specs=None,
                   quantize: bool = False):
    """Build a jitted LTFB tournament step over a trainer mesh axis.

    The returned ``step(params_stacked, batch_stacked, round_idx)`` uses a
    ``lax.switch`` over the log2(K) butterfly pairings, so every round is
    one compiled program with static collective-permutes.
    """
    from jax.sharding import PartitionSpec as P

    n_bits = max(1, num_trainers.bit_length() - 1)
    perms = [pairing_to_perm(butterfly_pairing(num_trainers, r))
             for r in range(n_bits)]

    in_spec = P(axis)

    def body(params, batch, round_idx):
        # shard_map delivers (1, ...)-shaped per-trainer blocks
        params = _squeeze0(params)
        batch = _squeeze0(batch)

        def mk_branch(perm):
            def branch(p, b):
                return tournament_shard(p, b, metric_fn, perm, axis, scope,
                                        quantize=quantize)
            return branch

        branches = [mk_branch(p) for p in perms]
        new_params, m_local, m_other = jax.lax.switch(
            round_idx % n_bits, branches, params, batch)
        return (_unsqueeze0(new_params), jnp.reshape(m_local, (1,)),
                jnp.reshape(m_other, (1,)))

    shard_fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs if param_specs is not None else in_spec,
                  batch_specs if batch_specs is not None else in_spec,
                  P()),
        out_specs=(param_specs if param_specs is not None else in_spec,
                   in_spec, in_spec), check_vma=False)
    return jax.jit(shard_fn)


# ---------------------------------------------------------------------------
# Host-side tournament (population trainer / benchmarks)
# ---------------------------------------------------------------------------


def tree_nbytes(tree) -> int:
    """Byte size of a pytree from array metadata (exchange-volume
    accounting) — never copies device buffers to host."""
    return int(sum(leaf.nbytes if hasattr(leaf, "nbytes")
                   else np.asarray(leaf).nbytes
                   for leaf in jax.tree.leaves(tree)))


def host_tournament(population: List[Params], metrics_eval: Callable,
                    partner: np.ndarray, scope: str = "full",
                    telemetry=None
                    ) -> Tuple[List[Params], Dict[str, Any]]:
    """One tournament round over an explicit population.

    metrics_eval(trainer_idx, candidate_params) -> float (lower better);
    candidate evaluation uses trainer_idx's LOCAL tournament data.
    ``telemetry`` (a :class:`repro.train.telemetry.TrainTelemetry`)
    gets one ``partner_exchange`` span per receiving trainer.
    """
    import time

    K = len(population)
    winners: List[Params] = [None] * K
    log = {"exchanged": 0, "kept_local": 0, "metrics": [],
           "exchange_bytes": 0}
    for i in range(K):
        j = int(partner[i])
        if j == i:
            winners[i] = population[i]
            log["kept_local"] += 1
            continue
        x0 = time.perf_counter()
        exch_j, _ = split_scope(population[j], scope)
        _, local_i = split_scope(population[i], scope)
        cand = merge_scope(exch_j, local_i, scope)
        nbytes = tree_nbytes(exch_j)
        log["exchange_bytes"] += nbytes
        if telemetry is not None:
            telemetry.trainer_span("partner_exchange", i, x0,
                                   time.perf_counter(),
                                   phase="partner_exchange",
                                   partner=j, bytes=nbytes)
        m_local = float(metrics_eval(i, population[i]))
        m_other = float(metrics_eval(i, cand))
        if m_other < m_local:
            winners[i] = cand
            log["exchanged"] += 1
        else:
            winners[i] = population[i]
            log["kept_local"] += 1
        log["metrics"].append((i, j, m_local, m_other))
    return winners, log


def host_tournament_async(population: List[Params], metrics_eval: Callable,
                          partner: np.ndarray, scope: str = "full",
                          executor=None, telemetry=None
                          ) -> Tuple[List[Params], Dict[str, Any]]:
    """Tournament round with evaluation overlapped with the exchange.

    The paper's non-blocking sendrecv: each trainer evaluates its OWN
    model on the held-out tournament set while the partner's model is in
    flight.  Here the local-metric evaluations are submitted to
    ``executor`` *before* the exchange (split/merge + byte accounting)
    runs, then the received-candidate evaluations are submitted, so the
    two phases overlap instead of strictly alternating per trainer.
    ``telemetry`` gets one ``partner_exchange`` span per receiving
    trainer (the eval spans come from ``metrics_eval`` itself).
    """
    import time

    if executor is None:
        return host_tournament(population, metrics_eval, partner, scope,
                               telemetry=telemetry)
    K = len(population)
    log = {"exchanged": 0, "kept_local": 0, "metrics": [],
           "exchange_bytes": 0}
    active = [i for i in range(K) if int(partner[i]) != i]
    # phase 1: local evals in flight while the exchange happens
    local_f = {i: executor.submit(metrics_eval, i, population[i])
               for i in active}
    cands: Dict[int, Params] = {}
    for i in active:
        j = int(partner[i])
        x0 = time.perf_counter()
        exch_j, _ = split_scope(population[j], scope)
        _, local_i = split_scope(population[i], scope)
        cands[i] = merge_scope(exch_j, local_i, scope)
        nbytes = tree_nbytes(exch_j)
        log["exchange_bytes"] += nbytes
        if telemetry is not None:
            telemetry.trainer_span("partner_exchange", i, x0,
                                   time.perf_counter(),
                                   phase="partner_exchange",
                                   partner=j, bytes=nbytes)
    # phase 2: received-candidate evals
    other_f = {i: executor.submit(metrics_eval, i, cands[i]) for i in active}
    winners = list(population)
    for i in range(K):
        j = int(partner[i])
        if j == i:
            log["kept_local"] += 1
            continue
        m_local = float(local_f[i].result())
        m_other = float(other_f[i].result())
        if m_other < m_local:
            winners[i] = cands[i]
            log["exchanged"] += 1
        else:
            log["kept_local"] += 1
        log["metrics"].append((i, j, m_local, m_other))
    return winners, log
