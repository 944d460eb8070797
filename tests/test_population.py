"""Population (host-orchestrated LTFB) behaviour tests on a tiny convex
problem where tournament dynamics are analytically predictable."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.population import Population, TrainerFns

TARGET = 3.0


def _fns(lr=0.2):
    def init(seed):
        rng = np.random.default_rng(seed)
        params = {"w": jnp.asarray(rng.normal(0, 2, (1,)), jnp.float32)}
        return params, {"step": 0}, {"lr": lr}

    @jax.jit
    def train_step(params, opt_state, batch, hparams):
        g = jax.grad(lambda p: jnp.mean((p["w"] - batch["t"]) ** 2))(params)
        params = {"w": params["w"] - hparams["lr"] * g["w"]}
        return params, opt_state, {"loss": jnp.mean(
            (params["w"] - batch["t"]) ** 2)}

    @jax.jit
    def metric(params, batch):
        return jnp.mean(jnp.abs(params["w"] - batch["t"]))

    return TrainerFns(init, train_step, metric)


def _mk_pop(K=4, seed=0, **kw):
    batch = {"t": jnp.full((4,), TARGET)}
    loaders = [lambda b=batch: b for _ in range(K)]
    tb = [[batch] for _ in range(K)]
    return Population(_fns(), loaders, tb, seed=seed, **kw), batch


def test_population_improves_and_tournament_propagates():
    pop, batch = _mk_pop(4)
    m0 = pop.best_metric(batch)
    pop.run(rounds=3, steps_per_round=10)
    m1 = pop.best_metric(batch)
    assert m1 < m0
    # all trainers should be near the best after several tournaments
    vals = [float(pop.fns.metric(t.params, batch)) for t in pop.trainers]
    assert max(vals) < 0.5


def test_hparam_perturbation_on_adoption():
    pop, batch = _mk_pop(4, perturb_hparams=True)
    lrs0 = [t.hparams["lr"] for t in pop.trainers]
    for _ in range(4):
        pop.train_round(3)
        pop.tournament()
    lrs1 = [t.hparams["lr"] for t in pop.trainers]
    assert lrs0 != lrs1      # losers perturbed their lr


def test_failure_and_recovery():
    pop, batch = _mk_pop(4)
    pop.run(rounds=2, steps_per_round=5)
    pop.fail(1)
    log = pop.tournament()   # must not raise; dead trainer self-pairs
    assert 1 not in [p for i, p in enumerate(log["partner"]) if i != p
                     and i == 1]
    pop.recover(1, from_best_of=batch)
    assert pop.trainers[1].alive
    # recovered trainer adopted the best model
    m_rec = float(pop.fns.metric(pop.trainers[1].params, batch))
    assert m_rec <= pop.best_metric(batch) + 1e-6


def test_elastic_resize_grow_and_shrink():
    pop, batch = _mk_pop(2)
    pop.run(rounds=2, steps_per_round=10)
    best = pop.best_metric(batch)
    loaders = [lambda b=batch: b for _ in range(5)]
    tb = [[batch] for _ in range(5)]
    pop.resize(5, loaders, tb, clone_batch=batch)
    assert len(pop.trainers) == 5
    # new trainers warm-started from the best
    m_new = float(pop.fns.metric(pop.trainers[4].params, batch))
    assert m_new <= best + 1e-6
    pop.resize(3, loaders[:3], tb[:3], clone_batch=batch)
    assert len(pop.trainers) == 3


def test_state_dict_roundtrip():
    pop, batch = _mk_pop(3)
    pop.run(rounds=1, steps_per_round=5)
    state = pop.state_dict()
    pop2, _ = _mk_pop(3, seed=0)
    pop2.load_state_dict(state)
    for a, b in zip(pop.trainers, pop2.trainers):
        np.testing.assert_array_equal(np.asarray(a.params["w"]),
                                      np.asarray(b.params["w"]))
    assert pop2.round == pop.round


class _SlowParams:
    """A params leaf whose pending device work takes ``DELAY`` seconds."""

    DELAY = 0.05

    def __init__(self):
        self.forced = 0

    def block_until_ready(self):
        time.sleep(self.DELAY)
        self.forced += 1
        return self


def test_train_seconds_cover_the_forced_step():
    def init(seed):
        return {"w": _SlowParams()}, {}, {"lr": 0.1}

    def train_step(params, opt_state, batch, hparams):
        return params, opt_state, {"loss": 0.0}

    pop = Population(TrainerFns(init, train_step, lambda p, b: 0.0),
                     [lambda: {}] * 2, [[{}]] * 2)
    pop.train_round(3)
    for t in pop.trainers:
        assert t.params["w"].forced == 3        # every step was forced
        assert t.train_seconds >= 3 * _SlowParams.DELAY
