"""End-to-end behaviour tests for the paper's system.

The full LTFB pipeline: synthetic JAG -> bundled files -> distributed
data store -> CycleGAN trainers -> tournament -> validation; plus the
checkpoint/restart lifecycle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.configs.icf_cyclegan import SMOKE as CCFG
from repro.core.population import Population, TrainerFns
from repro.data import jag
from repro.datastore.store import DataStore, PrefetchLoader, partition_files
from repro.train.steps import make_gan_steps


@pytest.fixture(scope="module")
def jag_data():
    xs = jag.sample_inputs(4096 + 512, seed=0)
    sim = jag.jag_simulate(xs, CCFG.image_size)
    return sim["x"], jag.flatten_outputs(sim)


def test_cyclegan_learns_on_jag(jag_data):
    """Paper Figs. 7/8 proxy: the surrogate must actually learn."""
    x, y = jag_data
    init, train_step, metric = make_gan_steps(
        CCFG, OptimizerConfig(name="adam", lr=1e-3))  # paper settings
    params, opt_state, hp = init(0)
    val = {"x": jnp.asarray(x[4096:]), "y": jnp.asarray(y[4096:])}
    m0 = float(metric(params, val))
    rng = np.random.default_rng(0)
    for _ in range(150):
        idx = rng.integers(0, 4096, 128)
        batch = {"x": jnp.asarray(x[idx]), "y": jnp.asarray(y[idx])}
        params, opt_state, _ = train_step(params, opt_state, batch, hp)
    m1 = float(metric(params, val))
    assert m1 < 0.6 * m0, (m0, m1)


def test_ltfb_beats_or_matches_k_independent(jag_data):
    """Paper Fig. 13: LTFB >= K-independent on held-out validation."""
    x, y = jag_data
    n, K = 4096, 4
    val = {"x": jnp.asarray(x[n:]), "y": jnp.asarray(y[n:])}
    init, train_step, metric = make_gan_steps(
        CCFG, OptimizerConfig(name="adam", lr=1e-3))
    fns = TrainerFns(init, train_step, metric)

    def mk():
        def loader_for(k):
            rng = np.random.default_rng(77 + k)
            pool = np.arange(k, n, K)
            def loader():
                idx = rng.choice(pool, 128)
                return {"x": jnp.asarray(x[idx]), "y": jnp.asarray(y[idx])}
            return loader
        loaders = [loader_for(k) for k in range(K)]
        tb = [[{"x": jnp.asarray(x[np.arange(k, n, K)[:256]]),
                "y": jnp.asarray(y[np.arange(k, n, K)[:256]])}]
              for k in range(K)]
        return loaders, tb

    loaders, tb = mk()
    ltfb = Population(fns, loaders, tb, scope="generator", seed=1,
                      perturb_hparams=False)
    ltfb.run(rounds=4, steps_per_round=25)
    v_ltfb = ltfb.best_metric(val)

    loaders, tb = mk()
    indep = Population(fns, loaders, tb, scope="generator", seed=1,
                       perturb_hparams=False)
    for _ in range(4):
        indep.train_round(25)
    v_ind = indep.best_metric(val)
    # identical data, seeds and step budget: the tournament may only help
    # (small-scale noise tolerance 25%)
    assert v_ltfb <= v_ind * 1.25, (v_ltfb, v_ind)


def test_full_pipeline_store_to_training(tmp_path):
    """Bundled files -> partitioned stores -> prefetch -> training."""
    paths = jag.write_bundles(str(tmp_path), 1000, 125,
                              image_size=CCFG.image_size, seed=0)
    part = partition_files(paths, 2, 0)          # trainer 0's partition
    store = DataStore(part, jag.read_bundle, num_ranks=2, mode="preload")
    store.preload()
    loader = PrefetchLoader(store, batch_size=64, depth=2)
    init, train_step, metric = make_gan_steps(CCFG, OptimizerConfig())
    params, opt_state, hp = init(0)
    try:
        for _ in range(5):
            raw = loader.next()
            batch = {"x": jnp.asarray(raw["x"]),
                     "y": jnp.asarray(jag.flatten_outputs(raw))}
            params, opt_state, m = train_step(params, opt_state, batch, hp)
        assert np.isfinite(float(m["g_loss"]))
    finally:
        loader.close()
    assert store.stats.file_opens == len(part)   # preload: one open each
