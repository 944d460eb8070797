"""Serving driver: open-loop chat sessions through the paged scheduler.

Set-up makes the weights from the seed on the device, builds one
``repro.serve.scheduler.Scheduler`` over a paged pool, compiles every
prefill bucket and block-table width the cell's traffic can reach, and
replays ``preroll_s`` seconds of the cell's own traffic so the window
opens on a loaded server.  The window then submits each request when
it is due (``Scheduler.submit``) and steps the scheduler
(``Scheduler.step``) in one thread, the way a single-process server
loop does.  Tokens are timed when a step hands them back.

End to end: time to first token from the moment each request was due
(80th percentile over the requests due in the window; those still
waiting when it closes are waited for), every gap between consecutive
tokens of a request that lands in the window (95th percentile), and the
output tokens handed back in the window per second.

Correct: samples of the finished requests, drawn from the seed with the
longest among them, are run through the float32 reference over prompt +
served tokens.  Every greedy token must lie within the limit of the
reference's best logit at its position.  The sampled tokens must look
drawn from the reference's distribution at their temperature: their
mean negative log-likelihood less the distribution's entropy, in
standard errors, stays within its limit (a sampler that ignores the
temperature, or reads another row's logits, leaves it by far).  Every
finished request must also have exactly the tokens it asked for.

Besides the metrics, a run reports the memory the server holds once its
set-up's transients are gone: bytes in use and pool pages in use when
the window closes, and the pool's high-water mark.
"""
from __future__ import annotations

import gc
import itertools
import time
from collections import deque
from typing import Dict, List

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.reference import qwen3 as ref
from benchmarks.chip.traffic import sessions


def model_config(cfg: dict):
    """The program's configuration, from the benchmark's file."""
    from repro.configs.base import ModelConfig

    s = ref.shapes(cfg)
    return ModelConfig(
        name=cfg.get("name", "qwen3"), family="dense", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["Hkv"],
        head_dim=s["D"], d_ff=s["ff"], vocab_size=s["V"], qk_norm=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=s["theta"], norm_eps=s["eps"],
        dtype=cfg["torch_dtype"])


def to_program(w: Dict) -> Dict:
    """The benchmark's weights in the program's parameter tree (the same
    arrays, no copy)."""
    block = {"ln1": {"scale": w["ln1"]},
             "mixer": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                       "wo": w["wo"], "q_norm": w["q_norm"],
                       "k_norm": w["k_norm"]},
             "ln2": {"scale": w["ln2"]},
             "ffn": {"wi": w["up"], "wg": w["gate"], "wo": w["down"]}}
    return {"embed": w["embed"], "body": (block,),
            "final_norm": {"scale": w["final_norm"]}}


def _check_tree(mcfg, params) -> None:
    import jax

    from repro.models.lm import init_lm

    want = jax.eval_shape(lambda k: init_lm(mcfg, k)[0],
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")


def _pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def reachable_shapes(sched, traffic: dict) -> Dict[str, List]:
    """Prefill (chunk bucket, table width) pairs and decode table widths
    that the traffic's parameters allow, by the scheduler's own
    bucketing rules: the same for every seed, so set-up does the same
    work whatever the draw."""
    pool = sched.pool
    cap = sched.max_seq
    bucket = lambda n: min(max(sched.min_prefill_bucket, _pow2(n)), cap)  # noqa
    width = lambda t: min(_pow2(pool.table_width_for(t)),                 # noqa
                          pool.max_blocks_per_seq)
    pmin, pmax, tmax = sessions.bounds(traffic)
    pairs = set()
    for P in range(pmin, pmax + 1):
        c = sched.min_prefill_bucket
        while True:
            pairs.add((bucket(c), width(P)))
            if c >= bucket(P):
                break
            c *= 2
    widths = sorted({width(t) for t in range(pmin + 1, tmax + 1)})
    return {"prefill": sorted(pairs), "decode": widths}


def warm(sched, traffic: dict, log) -> None:
    """Compile every shape the traffic can reach, through the session's
    own entry points, on a scratch reservation."""
    import jax.numpy as jnp

    shapes = reachable_shapes(sched, traffic)
    log(f"warm-up: {len(shapes['prefill'])} prefill shapes, decode "
        f"widths {shapes['decode']}")
    rid = "__warm__"
    sched.pool.admit(rid, sched.max_seq)
    for C, W in shapes["prefill"]:
        n = min(C, W * sched.pool.block_size)
        sched.pool.ensure(rid, n)
        sched.session.prefill_chunk(rid, np.ones((n,), np.int32), 0, n, C,
                                    W)
    sched.pool.release(rid)
    B = sched.pool.num_slots
    for W in shapes["decode"]:
        logits = sched.session.step(np.zeros((B, 1), np.int32),
                                    np.full((B,), -1, np.int32), width=W)
        np.asarray(logits.astype(jnp.float32))


class Observer:
    """Hands-back times of every token, read after each scheduler step."""

    def __init__(self, sched):
        self.sched = sched
        self.times: Dict[int, List[float]] = {}
        self.n_done = 0

    def after_step(self, t: float) -> None:
        s = self.sched
        for rid, act in s.active.items():
            self._seen(rid, len(act.tokens), t)
        for rid in itertools.islice(s.results, self.n_done, None):
            self._seen(rid, len(s.results[rid]), t)
            self.n_done += 1

    def _seen(self, rid, n: int, t: float) -> None:
        ts = self.times.setdefault(rid, [])
        if n > len(ts):
            ts.extend([t] * (n - len(ts)))


def measure(run) -> harness.Measured:
    import jax

    from repro.serve.scheduler import Request, Scheduler

    spec, cfg = run.spec, run.config
    mcfg = model_config(cfg)
    weights = ref.make_weights(cfg, harness.device_key(run.seed))
    params = to_program(weights)
    _check_tree(mcfg, params)
    sched = Scheduler(
        mcfg, params, num_slots=spec["slots"], max_len=spec["max_seq"],
        block_size=spec["block_size"], num_blocks=cfg["kv_pool_pages"],
        max_seq=spec["max_seq"], prefix_sharing=True,
        pin_prefix=spec["pin_prefix"],
        max_prefills_per_step=spec["prefill_per_step"],
        trace_capacity=1 << 20)
    reqs = sessions.generate(spec["traffic"], run.seed, run.seconds,
                             mcfg.vocab_size)
    harness.log(f"traffic: {sessions.summary(reqs)}")
    warm(sched, spec["traffic"], harness.log)
    jax.block_until_ready(sched.pool.cache)

    # decode calls made inside the traced part of the window: the lengths
    # each active row attends over, for the per-layer readers
    decode_calls: List[List[int]] = []
    recording = [False]
    inner_step = sched.session.step

    def step(tokens, index, **kw):
        if recording[0]:
            idx = np.asarray(index)
            decode_calls.append([int(i) + 1 for i in idx if i >= 0])
        return inner_step(tokens, index, **kw)

    sched.session.step = step

    obs = Observer(sched)
    pending = deque(reqs)
    preroll = float(spec["traffic"]["preroll_s"])
    seconds = run.seconds
    t_w = time.perf_counter() + preroll        # the window's opening
    submitted: Dict[int, float] = {}
    rejected: List[int] = []
    lags: Dict[int, float] = {}
    window_due = {r.rid for r in reqs if 0.0 <= r.due < seconds}
    opened = closed = False
    counters = {}
    live: List[tuple] = []      # (second, requests admitted and unfinished)
    wait_limit = float(spec["ttft_wait_s"])
    while True:
        now = time.perf_counter() - t_w
        if not opened and now >= 0.0:
            run.window_opens(at=t_w)
            opened = True
            counters["open"] = (sched.pool.prefix_shared_tokens,)
            run.trace_start()
        if opened and not closed:
            recording[0] = run.traced()
        if not closed and now >= len(live) - preroll:
            live.append((round(now), len(sched.active)
                         + len(sched.prefilling)))
        if opened and not closed and now >= seconds:
            closed = True
            recording[0] = False
            run.trace_window_ends()
            counters["close"] = (sched.pool.prefix_shared_tokens,)
            counters["backlog"] = len(sched.queue)
            counters["running"] = len(sched.active) + len(sched.prefilling)
            counters["memory"] = held_memory(run, sched.pool)
            run.window_closes()
        if closed:
            # every request due in the window is waited for, also one
            # that falls due just before the close and is submitted below
            waiting = [r for r in window_due if r not in rejected
                       and not obs.times.get(r)]
            if not waiting or now >= seconds + wait_limit:
                break
        with run.span("gen.submit"):
            while pending and pending[0].due <= now:
                r = pending.popleft()
                req = Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                              temperature=r.temperature,
                              seed=r.sample_seed)
                try:
                    sched.submit(req)
                    submitted[r.rid] = r.due
                    lags[r.rid] = now - r.due
                except ValueError:
                    rejected.append(r.rid)
        if sched.queue or sched.active or sched.prefilling:
            with run.span("serve.step"):
                sched.step()
            obs.after_step(time.perf_counter() - t_w)
        else:
            nxt = pending[0].due if pending else seconds
            with run.span("gen.wait"):
                time.sleep(max(0.0, min(nxt - now, 0.05)))
    run.trace_stop()

    # ---- end-to-end ------------------------------------------------------
    due_of = {r.rid: r.due for r in reqs}
    ttft = [obs.times[r][0] - due_of[r] for r in window_due
            if obs.times.get(r)]
    itl, tokens_in = [], 0
    for rid, ts in obs.times.items():
        for a, b in zip(ts[:-1], ts[1:]):
            if 0.0 <= b < seconds:
                itl.append(b - a)
        tokens_in += sum(0.0 <= t < seconds for t in ts)
    failed = len([r for r in window_due if not obs.times.get(r)])
    in_window = [n for t, n in live if t >= 0]
    harness.log(f"live requests each 5 s from the pre-roll's start: "
                f"{[n for _, n in live[::5]]}; at the opening "
                f"{in_window[0]}, mean in the window "
                f"{sum(in_window) / len(in_window):.1f}")
    harness.log(f"held at the close: {counters['memory']}")
    # a tail is read at the highest percentile with ten samples beyond
    # it: the 80th of the window's ~51 first tokens, the 95th of its
    # thousands of gaps
    metrics = {"ttft_p80_ms": harness.percentile(ttft, 80) * 1e3,
               "itl_p95_ms": harness.percentile(itl, 95) * 1e3,
               "serve_tokens_per_s": tokens_in / seconds}
    harness.log(f"window: due={len(window_due)} first tokens={len(ttft)} "
                f"gaps={len(itl)} tokens={tokens_in} rejected="
                f"{len(rejected)} ttft_p50_ms="
                f"{harness.percentile(ttft, 50) * 1e3:.1f} ttft_p95_ms="
                f"{harness.percentile(ttft, 95) * 1e3:.1f} itl_p50_ms="
                f"{harness.percentile(itl, 50) * 1e3:.1f} "
                f"{metrics}")

    # ---- per-layer observations -----------------------------------------
    tracer = sched.telemetry.tracer
    admit = {}
    for ev in tracer.events:
        if ev.get("name") == "admit" and ev.get("ph") == "i":
            admit[int(ev["args"]["rid"])] = \
                tracer.epoch + ev["ts"] / 1e6 - t_w
    queue_waits = [admit[r] - due_of[r] for r in window_due if r in admit]
    admitted_in = [r for r, t in admit.items() if 0.0 <= t < seconds]
    prompt_of = {r.rid: len(r.prompt) for r in reqs}
    observations = {
        "window_s": seconds,
        "queue_waits_s": queue_waits,
        "gen_lags_s": [lags[r] for r in window_due if r in lags],
        "prompt_tokens_admitted": sum(prompt_of[r] for r in admitted_in),
        "prefix_shared_tokens": counters["close"][0] - counters["open"][0],
        "decode_calls": decode_calls,
        "block_size": spec["block_size"],
        "queued_at_close": counters["backlog"],
        "running_at_close": counters["running"],
        "live_at_open": in_window[0],
        "live_window_mean": sum(in_window) / len(in_window),
    }

    # ---- what the comparison needs, then the program's state goes -------
    finished = {rid: np.asarray(toks) for rid, toks in sched.results.items()
                if not isinstance(rid, str)}
    by_rid = {r.rid: r for r in reqs}

    def check(control: bool = False) -> List[harness.Check]:
        nonlocal sched, params
        sched.session.step = inner_step
        del sched, params
        gc.collect()
        return compare(run, cfg, weights, by_rid, finished, control)

    return harness.Measured(metrics=metrics, attempted=len(window_due),
                            failed=failed, observations=observations,
                            check=check, report=counters["memory"])


def held_memory(run, pool) -> dict:
    """What the server holds in steady state: device bytes in use on the
    fullest chip, and the pool's pages in use (prefix pages kept pinned
    included), its high-water mark and its size."""
    pages = [sh.blocks for sh in pool.shards]
    return {"bytes_in_use": run.memory_in_use_bytes(),
            "kv_pages_in_use": sum(b.used_blocks for b in pages),
            "kv_pages_high_water": sum(b.high_water for b in pages),
            "kv_pages": sum(b.num_blocks for b in pages)}


def sample_for_check(by_rid, finished, seed: int, want_tokens: int,
                     greedy: bool = True):
    """Finished greedy (or sampled) requests: the longest, then others
    drawn from the seed until ``want_tokens`` served tokens are
    covered."""
    pool = sorted(r for r in finished if by_rid[r].greedy == greedy)
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(by_rid[r].prompt)
                  + len(finished[r]))
    rest = [r for r in pool if r != longest]
    order = np.random.default_rng([seed, 0xC4EC, int(greedy)]).permutation(
        len(rest))
    picked, total = [longest], len(finished[longest])
    for i in order:
        if total >= want_tokens:
            break
        picked.append(rest[i])
        total += len(finished[rest[i]])
    return picked


def served_gap(cfg, weights, by_rid, finished, picked,
               control=None) -> float:
    """Widest gap over the picked requests' served tokens (or, given the
    control's weights, over the tokens its float8 forward puts first)."""
    worst = 0.0
    for rid in picked:
        g = ref.served_gaps(cfg, weights, by_rid[rid].prompt, finished[rid],
                            control)
        worst = max(worst, float(g.max()))
    return worst


def sampled_z(cfg, weights, by_rid, finished, picked) -> float:
    """How far the picked sampled requests' tokens are, in standard
    errors, from what draws from the reference's distribution give: the
    summed excess of negative log-likelihood over entropy, over the
    square root of its summed variance (about N(0, 1) for a sound
    sampler; a wrong temperature or another row's logits shift it by a
    fixed amount per token)."""
    total = var = 0.0
    for rid in picked:
        e, v = ref.sampled_surprise(cfg, weights, by_rid[rid].prompt,
                                    finished[rid], by_rid[rid].temperature)
        total += float(e.sum())
        var += float(v.sum())
    return abs(total) / max(var, 1e-30) ** 0.5


def compare(run, cfg, weights, by_rid, finished,
            control: bool = False) -> List[harness.Check]:
    spec = run.spec
    lim = spec["limits"]
    wrong_len = sum(len(t) != by_rid[r].max_new for r, t in finished.items())
    V = cfg["vocab_size"]
    out_of_vocab = sum(int(((t < 0) | (t >= V)).sum())
                       for t in finished.values())
    picked = sample_for_check(by_rid, finished, run.seed,
                              spec["check_tokens"])
    drawn = sample_for_check(by_rid, finished, run.seed,
                             spec["check_sampled_tokens"], greedy=False)
    t0 = time.perf_counter()
    n_tok = sum(len(finished[r]) for r in picked)
    n_drawn = sum(len(finished[r]) for r in drawn)
    gap = served_gap(cfg, weights, by_rid, finished, picked) \
        if picked else float("inf")
    z = sampled_z(cfg, weights, by_rid, finished, drawn) \
        if drawn else float("inf")
    checks = [harness.Check("served_logit_gap", gap,
                            lim["served_logit_gap"]),
              harness.Check("sampled_nll_z", z, lim["sampled_nll_z"]),
              harness.Check("length_errors", float(wrong_len + out_of_vocab),
                            0.0)]
    if control and picked:
        checks.append(harness.Check(
            "control_served_logit_gap",
            served_gap(cfg, weights, by_rid, finished, picked,
                       ref.quantized(weights)),
            lim["served_logit_gap"]))
    harness.log(f"reference: {len(picked)} greedy requests, {n_tok} served "
                f"tokens; {len(drawn)} sampled requests, {n_drawn} served "
                f"tokens; {time.perf_counter() - t0:.1f} s")
    return checks
