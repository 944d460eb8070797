"""Training driver: the paper's LTFB job through the tournament
orchestrator.

Set-up makes the JAG samples and every trainer's weights from the seed
on the device, builds one ``repro.core.tournament.TournamentOrchestrator``
(K trainers, each with its own preloaded datastore partition and
prefetch thread) and drives it through its first three steps with its
own ``train_round`` and its first ``tournament``, which compiles every
program the window runs.  The window then runs whole rounds —
``train_round(steps_per_round)`` and ``tournament()`` — until
``--seconds`` have passed.

End to end: samples stepped by all trainers over the whole window,
tournaments included, per second.

Correct: the float32 reference follows every trainer's first three
steps on the same batches from the same weights.  Compared: each
step's generator and discriminator loss; trainer 0's first gradient as
Adam received it (from its first moment after one step) and its
parameters' change over the three steps, leaf by leaf; the tournament
metric of each trainer's own and received generator; and whether each
trainer came out of the tournament holding the generator the reference
chooses.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.reference import cyclegan as ref
from benchmarks.chip.traffic import jag


def program_config(cfg: dict):
    from repro.configs.icf_cyclegan import CycleGANConfig

    keys = ("input_dim", "num_scalars", "num_images", "image_size",
            "latent_dim", "w_forward", "w_cycle", "w_adv", "w_recon",
            "dtype")
    kw = {k: cfg[k] for k in keys}
    for k in ("fwd_hidden", "inv_hidden", "disc_hidden", "enc_hidden",
              "dec_hidden"):
        kw[k] = tuple(cfg[k])
    return CycleGANConfig(**kw)


def weights_key(seed: int, trainer: int):
    """Trainer ``trainer``'s weights key: the benchmark's, from the seed."""
    import jax

    return jax.random.fold_in(harness.device_key(seed), trainer)


def build(run, cfg: dict, spec: dict):
    """The orchestrator over the benchmark's data and weights."""
    import jax

    from repro.configs.base import OptimizerConfig
    from repro.core.population import TrainerFns
    from repro.core.tournament import (DataPlan, TournamentConfig,
                                       TournamentOrchestrator)
    from repro.train.steps import make_gan_steps

    o = cfg["optimizer"]
    opt = OptimizerConfig(name=o["name"], lr=o["lr"], b1=o["b1"],
                          b2=o["b2"], eps=o["eps"], warmup_steps=1)
    prog_init, train_step, metric = make_gan_steps(program_config(cfg), opt)
    made: List[int] = []       # trainers initialised so far

    def init(trainer_seed: int):
        prog_params, opt_state, hparams = prog_init(trainer_seed)
        params = ref.make_params(cfg, weights_key(run.seed, len(made)))
        same = jax.tree.map(lambda a, b: a.shape == b.shape
                            and a.dtype == b.dtype, prog_params, params)
        if not all(jax.tree.leaves(same)):
            raise ValueError("benchmark weights do not match the "
                             "program's parameter tree")
        made.append(trainer_seed)
        return params, opt_state, hparams

    n, per_file = spec["traffic"]["samples"], spec["traffic"]["samples_per_file"]
    data = jag.generate(n, cfg["image_size"], run.seed)
    files = [f"jag_{i:05d}" for i in range(n // per_file)]

    def reader(name: str) -> Dict[str, np.ndarray]:
        i = int(name.split("_")[1]) * per_file
        return {k: v[i:i + per_file] for k, v in data.items()}

    plan = DataPlan.jag_cyclegan(files)
    plan.reader = reader
    tcfg = TournamentConfig(
        trainers=spec["trainers"], scope=spec["scope"],
        backend=spec["backend"], store_mode="preload",
        num_ranks=spec["num_ranks"], partition="stride",
        batch_size=cfg["batch"],
        tournament_batch_size=min(2 * cfg["batch"], per_file),
        seed=run.seed)
    orch = TournamentOrchestrator(TrainerFns(init, train_step, metric),
                                  plan, tcfg)
    return orch


def first_steps(orch, steps: int):
    """Drive every trainer through its first ``steps`` steps with the
    window's own call, recording what the comparison needs: the batches
    fed, each step's losses, trainer 0's first moment after step 1."""
    import jax

    trainers = orch.population.trainers
    fed: List[List[dict]] = [[] for _ in trainers]
    inner = [t.loader for t in trainers]
    for i, t in enumerate(trainers):
        def rec(i=i):
            b = inner[i]()
            fed[i].append({k: np.array(v) for k, v in b.items()})
            return b
        t.loader = rec
    losses: List[List[dict]] = [[] for _ in trainers]
    m1 = None
    for s in range(steps):
        orch.train_round(1)
        for i, t in enumerate(trainers):
            losses[i].append({k: float(t.last_metrics[k])
                              for k in ("g_loss", "d_loss")})
        if s == 0:
            m1 = jax.device_get({k: t0["m"] for k, t0 in
                                 trainers[0].opt_state.items()})
    for i, t in enumerate(trainers):
        t.loader = inner[i]
    params3 = jax.device_get(trainers[0].params)
    # one small leaf of every generator identifies whose generator a
    # trainer holds after the tournament
    marks = [np.array(jax.device_get(t.params["gen"]["fwd"]["w"][0]))
             for t in trainers]
    return {"fed": fed, "losses": losses, "m1": m1, "params3": params3,
            "marks": marks}


def measure(run) -> harness.Measured:
    import jax

    spec, cfg = run.spec, run.config
    orch = build(run, cfg, spec)
    rec = first_steps(orch, 3)
    tlog = orch.tournament()
    held = [np.array(jax.device_get(t.params["gen"]["fwd"]["w"][0]))
            for t in orch.population.trainers]
    tournament_sets = [
        {k: np.concatenate([np.asarray(b[k]) for b in batches])
         for k in batches[0]}
        for batches in orch._tournament_batches]
    # one round of the window's shape, so nothing compiles inside it
    orch.train_round(1)
    jax.block_until_ready([t.params for t in orch.population.trainers])

    trainers = orch.population.trainers
    spr = spec["traffic"]["steps_per_round"]
    before = [(t.steps, t.data_wait_seconds) for t in trainers]
    tournament_s = 0.0
    rounds = 0
    t0 = run.window_opens()
    run.trace_start()
    while True:
        with run.span("ltfb.train_round"):
            orch.train_round(spr)
        a = time.perf_counter()
        with run.span("ltfb.tournament"):
            orch.tournament()
        tournament_s += time.perf_counter() - a
        rounds += 1
        run.traced()
        if time.perf_counter() - t0 >= run.seconds:
            break
    window_s = time.perf_counter() - t0
    run.trace_stop()
    run.window_closes()
    steps = sum(t.steps - s for t, (s, _) in zip(trainers, before))
    wait = sum(t.data_wait_seconds - w for t, (_, w) in zip(trainers, before))
    samples = steps * cfg["batch"]
    metrics = {"train_samples_per_s": samples / window_s}
    harness.log(f"window: {rounds} rounds, {steps} steps, {samples} samples "
                f"in {window_s:.3f} s; tournaments {tournament_s:.3f} s; "
                f"data wait {wait:.3f} s; {metrics}")
    observations = {"window_s": window_s, "tournament_s": tournament_s,
                    "data_wait_s": wait, "steps": steps,
                    "batch": cfg["batch"], "rounds": rounds}

    def check(control: bool = False) -> List[harness.Check]:
        nonlocal orch
        orch.close()
        del orch
        gc.collect()
        return compare(run, cfg, rec, tlog, held, tournament_sets, control)

    return harness.Measured(metrics=metrics, attempted=steps, failed=0,
                            observations=observations, check=check)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def leaf_norms(tree) -> Dict[str, float]:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x)))
            for p, x in flat}


def worst_leaf_gap(prog: Dict[str, float], want: Dict[str, float],
                   keep=None) -> float:
    """Largest |norm_prog - norm_ref| over leaves, each against the larger
    of its own reference norm and the median leaf's."""
    med = float(np.median(list(want.values())))
    return max(abs(prog[k] - want[k]) / max(want[k], med)
               for k in want if keep is None or k in keep)


def reference_readings(run, cfg: dict, rec: dict, trainers: int,
                       dtype=np.float32, share: float = 1.0) -> dict:
    """What the reference (or, with ``dtype``/``share``, the control and
    the half-batch fault) gives for the recorded steps: per trainer the
    losses and the parameters after them; trainer 0's first gradient and
    its change over the steps."""
    import jax

    out = {"losses": [], "params": [], "grads0": None, "change0": None}
    for i in range(trainers):
        p = ref.make_params(cfg, weights_key(run.seed, i))
        p0 = jax.device_get(p) if i == 0 else None
        st = ref.init_state(p)
        ls = []
        for s, batch in enumerate(rec["fed"][i]):
            p, st, loss, grads = ref.step(p, st, batch, cfg, dtype, share)
            ls.append({k: float(v) for k, v in loss.items()})
            if i == 0 and s == 0:
                out["grads0"] = jax.device_get(grads)
        out["losses"].append(ls)
        out["params"].append(p)
        if i == 0:
            p3 = jax.device_get(p)
            out["change0"] = jax.tree.map(lambda a, b: a - b, p3, p0)
    return out


def readings(cfg: dict, rec: dict, tlog: dict, held, sets, want: dict
             ) -> Dict[str, float]:
    """The numbers compared, for the program's record against ``want``."""
    import jax

    b1 = cfg["optimizer"]["b1"]
    loss_gap = max(abs(got[s][k] - exp[s][k]) / abs(exp[s][k])
                   for got, exp in zip(rec["losses"], want["losses"])
                   for s in range(len(exp)) for k in exp[s])
    g_prog = leaf_norms(jax.tree.map(lambda m: m / (1 - b1), rec["m1"]))
    g_ref = leaf_norms(want["grads0"])
    med = float(np.median(list(g_ref.values())))
    moving = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    d_prog = leaf_norms(jax.tree.map(lambda a, b: a - b, rec["params3"],
                                     rec["params0"]))
    d_ref = leaf_norms(want["change0"])
    # tournament: own and received generator on the trainer's held-out set
    t_gap, wrong = 0.0, 0
    margin = cfg["decision_margin"]
    for i, j, m_local, m_other in tlog["metrics"]:
        r_local = ref.tournament_metric(want["params"][i]["gen"], sets[i])
        r_other = ref.tournament_metric(want["params"][j]["gen"], sets[i])
        t_gap = max(t_gap, abs(m_local - r_local) / abs(r_local),
                    abs(m_other - r_other) / abs(r_other))
        if abs(r_other - r_local) > margin * abs(r_local):
            src = j if r_other < r_local else i
            wrong += int(not np.array_equal(held[i], rec["marks"][src]))
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(g_prog, g_ref),
            "change_gap": worst_leaf_gap(d_prog, d_ref, moving),
            "tournament_gap": t_gap,
            "adoption_errors": float(wrong),
            "leaves_left_out": float(len(g_ref) - len(moving))}


NUMBERS = ("loss_gap", "grad_gap", "change_gap", "tournament_gap",
           "adoption_errors")


def compare(run, cfg, rec, tlog, held, sets,
            control: bool = False) -> List[harness.Check]:
    """The checks; with ``control``, also the readings of the control
    (the reference in bfloat16) and of two planted faults (half of each
    batch left out of the means; the tournament's exchange left out,
    which hands each trainer its own generator back as the received one)
    against the float32 reference, under ``control.*``, ``half_batch.*``
    and ``no_exchange.*``."""
    import jax

    t0 = time.perf_counter()
    k = len(rec["fed"])
    want = reference_readings(run, cfg, rec, k)
    rec = dict(rec)
    rec["params0"] = jax.device_get(
        ref.make_params(cfg, weights_key(run.seed, 0)))
    lim = run.spec["limits"]
    both = {**cfg, **lim}
    got = readings(both, rec, tlog, held, sets, want)
    harness.log(f"reference: {k} trainers x {len(rec['fed'][0])} steps, "
                f"{time.perf_counter() - t0:.1f} s; {got}")
    checks = [harness.Check(n, float(got[n]), float(lim[n]))
              for n in NUMBERS]
    if control:
        for tag, kw in (("control", {"dtype": jax.numpy.bfloat16}),
                        ("half_batch", {"share": 0.5})):
            low = reference_readings(run, cfg, rec, k, **kw)
            fake = as_program_record(cfg, rec, low, tlog, sets,
                                     kw.get("dtype", np.float32))
            r = readings(both, fake["rec"], fake["tlog"], fake["held"],
                         sets, want)
            checks += [harness.Check(f"{tag}.{n}", float(r[n]),
                                     float(lim[n])) for n in NUMBERS]
        swapped = {**tlog, "metrics": [(i, j, m_l, m_l) for i, j, m_l, _
                                       in tlog["metrics"]]}
        r = readings(both, rec, swapped, rec["marks"], sets, want)
        checks += [harness.Check(f"no_exchange.{n}", float(r[n]),
                                 float(lim[n])) for n in NUMBERS]
    return checks


def as_program_record(cfg, rec, low, tlog, sets, dtype) -> dict:
    """A record as the program would have left it had it computed what
    ``low`` (the control, or a planted fault, in the program's place)
    computed: its losses, first moment, parameters and tournament."""
    import jax

    b1 = cfg["optimizer"]["b1"]
    new = dict(rec)
    new["losses"] = low["losses"]
    new["m1"] = jax.tree.map(lambda g: (1 - b1) * np.asarray(g),
                             low["grads0"])
    new["params3"] = jax.tree.map(lambda d, p0: np.asarray(d) + p0,
                                  low["change0"], rec["params0"])
    marks = [np.asarray(p["gen"]["fwd"]["w"][0]) for p in low["params"]]
    new["marks"] = marks
    metrics, held = [], []
    for i, j, _, _ in tlog["metrics"]:
        m_l = ref.tournament_metric(low["params"][i]["gen"], sets[i], dtype)
        m_o = ref.tournament_metric(low["params"][j]["gen"], sets[i], dtype)
        metrics.append((i, j, m_l, m_o))
    for i, j, m_l, m_o in metrics:
        held.append(marks[j] if m_o < m_l else marks[i])
    return {"rec": new, "tlog": {**tlog, "metrics": metrics},
            "held": held}
