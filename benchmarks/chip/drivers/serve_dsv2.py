"""Serving driver for DeepSeek-V2: long-document questions through the
paged scheduler, with latent attention and 64-expert MoE.

The same open loop as ``drivers/serve.py`` (whose traffic, token
observer, memory report and request sampling it imports): weights from
the seed on the device, one ``repro.serve.scheduler.Scheduler`` over the
paged latent pool, every reachable shape compiled, a pre-roll of the
cell's own traffic, then a window in which each request is submitted
when due and the scheduler stepped in one thread.  What differs:

* the model mapping: the DeepSeek-V2 configuration and weights
  (``reference/deepseek_v2.py``) in the program's tree;
* prompts are prefilled in chunks of the workload's ``prefill_chunk``
  (a 32k-token document cannot go through the dropless MoE in one
  call), so set-up compiles the (chunk, table width) pairs that chunking
  reaches;
* set-up first prefills each shared document once (a request of the
  document and one question token), so the documents are pinned in the
  pool before the pre-roll's questions arrive;
* the routing counters: zeroed on the device when the window opens and
  copied to the host once when it closes (per-expert routed tokens and
  dropped choices);
* the comparison runs the float32 DeepSeek-V2 reference, and also
  requires that no routed choice was dropped.

End to end and ``correct`` as in ``drivers/serve.py``.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from pathlib import Path
from typing import Dict, List

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.reference import deepseek_v2 as ref
from benchmarks.chip.traffic import sessions

serve = harness.load_file(Path(__file__).resolve().parent / "serve.py",
                          "bench_driver_serve_base")


def model_config(cfg: dict):
    """The program's configuration, from the benchmark's file."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    YarnConfig)

    unsupported = {"q_lora_rank": None, "routed_scaling_factor": 1,
                   "scoring_func": "softmax", "topk_method": "greedy",
                   "n_group": 1, "topk_group": 1, "moe_layer_freq": 1}
    for k, v in unsupported.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"{k}={cfg[k]!r} is not supported")
    s = ref.shapes(cfg)
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg.get("name", "deepseek-v2"), family="moe",
        num_layers=s["L"], d_model=s["d"], num_heads=s["H"],
        num_kv_heads=s["H"], d_ff=s["fe"], vocab_size=s["V"],
        rope_theta=s["theta"], norm_eps=s["eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"],
        moe=MoEConfig(num_experts=s["E"], top_k=s["k"],
                      num_shared_experts=s["shared"], d_expert=s["fe"],
                      first_k_dense=s["k0"], dense_d_ff=s["ff"],
                      norm_topk_prob=s["norm_topk"]),
        mla=MLAConfig(kv_lora_rank=s["r_kv"], qk_nope_head_dim=s["n"],
                      qk_rope_head_dim=s["r"], v_head_dim=s["v"]),
        yarn=YarnConfig(factor=float(rs["factor"]),
                        original_max_position=int(
                            rs["original_max_position_embeddings"]),
                        beta_fast=float(rs["beta_fast"]),
                        beta_slow=float(rs["beta_slow"]),
                        mscale=float(rs["mscale"]),
                        mscale_all_dim=float(rs["mscale_all_dim"])))


def to_program(w: Dict) -> Dict:
    """The benchmark's weights in the program's parameter tree (the same
    arrays, no copy; the router is upcast to the program's float32)."""
    import jax.numpy as jnp

    def block(p, ffn):
        return {"ln1": {"scale": p["ln1"]},
                "mixer": {k: p[k] for k in ("wq", "wkv_a", "kv_norm",
                                            "wkv_b", "wo")},
                "ln2": {"scale": p["ln2"]}, "ffn": ffn}

    dl, ml = w["dense_layers"], w["moe_layers"]
    moe = {"router": ml["router"].astype(jnp.float32), "wi": ml["e_up"],
           "wg": ml["e_gate"], "wo": ml["e_down"],
           "shared": {"wi": ml["s_up"], "wg": ml["s_gate"],
                      "wo": ml["s_down"]}}
    return {"embed": w["embed"],
            "prefix": block(dl, {"wi": dl["up"], "wg": dl["gate"],
                                 "wo": dl["down"]}),
            "body": (block(ml, moe),),
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": w["lm_head"]}


def reachable_shapes(sched, traffic: dict) -> Dict[str, List]:
    """Prefill (chunk bucket, table width) pairs and decode table widths
    that the traffic's parameters allow under chunked prefill, by the
    scheduler's own bucketing rules: a prompt starts at 0 (a document
    not yet cached) or after a shared prefix (a whole number of pages
    past the document's start), and is cut into chunks of
    ``prefill_chunk``; the same for every seed."""
    C = sched.prefill_chunk
    bs = sched.pool.block_size
    pmin, pmax, tmax = sessions.bounds(traffic)
    shared = range(traffic["system_prompt_tokens"] // bs * bs, pmax, bs)
    pairs = set()
    for P in range(pmin, pmax + 1):
        for start in [0, *(s for s in shared if s < P)]:
            pos = start
            while pos < P:
                n = min(C, P - pos)
                final = pos + n >= P
                Cb = C if not final or n == C else sched._bucket(n, cap=C)
                pairs.add((Cb, sched._table_bucket(pos + n)))
                pos += n
    widths = sorted({sched._table_bucket(t)
                     for t in range(pmin + 1, tmax + 1)})
    return {"prefill": sorted(pairs), "decode": widths}


def warm(sched, traffic: dict, log) -> None:
    """Compile every shape the traffic can reach, through the session's
    own entry points, on a scratch reservation."""
    import jax.numpy as jnp

    shapes = reachable_shapes(sched, traffic)
    log(f"warm-up: {len(shapes['prefill'])} prefill shapes "
        f"{shapes['prefill']}, decode widths {shapes['decode']}")
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


def pin_documents(sched, reqs, traffic: dict, log) -> None:
    """Prefill each shared document once (the document and one token of
    question, one token answered), so its pages are registered and
    pinned before the pre-roll."""
    from repro.serve.scheduler import Request

    n_sys = int(traffic["system_prompt_tokens"])
    docs = {}
    for r in reqs:
        docs.setdefault(r.system, r.prompt[:n_sys])
    t0 = time.perf_counter()
    for i, doc in sorted(docs.items()):
        sched.submit(Request(rid=f"__doc{i}__", prompt=np.concatenate(
            [doc, doc[:1]]).astype(np.int32), max_new=1, temperature=0.0))
    while sched.queue or sched.active or sched.prefilling:
        sched.step()
    log(f"documents pinned: {len(docs)} of {n_sys} tokens in "
        f"{time.perf_counter() - t0:.1f} s, pool "
        f"{sched.pool.blocks.used_blocks} pages in use")


def measure(run) -> harness.Measured:
    import jax

    from repro.serve.scheduler import Request, Scheduler

    spec, cfg = run.spec, run.config
    mcfg = model_config(cfg)
    weights = ref.make_weights(cfg, harness.device_key(run.seed))
    params = to_program(weights)
    serve._check_tree(mcfg, params)
    sched = Scheduler(
        mcfg, params, num_slots=spec["slots"], max_len=spec["max_seq"],
        block_size=spec["block_size"], num_blocks=cfg["kv_pool_pages"],
        max_seq=spec["max_seq"], prefill_chunk=spec["prefill_chunk"],
        prefix_sharing=True, pin_prefix=spec["pin_prefix"],
        max_prefills_per_step=spec["prefill_per_step"],
        trace_capacity=1 << 20)
    reqs = sessions.generate(spec["traffic"], run.seed, run.seconds,
                             mcfg.vocab_size)
    harness.log(f"traffic: {sessions.summary(reqs)}")
    warm(sched, spec["traffic"], harness.log)
    pin_documents(sched, reqs, spec["traffic"], harness.log)
    sched.pool.reset_moe_counts()       # compiled here, not in the window
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

    obs = serve.Observer(sched)
    pending = deque(reqs)
    preroll = float(spec["traffic"]["preroll_s"])
    seconds = run.seconds
    t_w = time.perf_counter() + preroll        # the window's opening
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
            sched.pool.reset_moe_counts()
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
            counters["memory"] = serve.held_memory(run, sched.pool)
            counters["moe"] = sched.read_moe_counters()
            run.window_closes()
        if closed:
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
    moe = counters["moe"]
    harness.log(f"routed tokens per expert in the window (summed over "
                f"layers): {moe['expert_tokens'].sum(axis=0).tolist()}; "
                f"dropped {int(moe['dropped_tokens'].sum())}; latent pool "
                f"{sched.stats.latent_cache_bytes_per_token} B a token")
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
        if ev.get("name") == "admit" and ev.get("ph") == "i" \
                and str(ev["args"]["rid"]).isdigit():
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
        "expert_tokens": moe["expert_tokens"].tolist(),
        "moe_dropped_tokens": int(moe["dropped_tokens"].sum()),
        "trace_dir": str(run.trace_dir),
    }

    # ---- what the comparison needs, then the program's state goes -------
    finished = {rid: np.asarray(toks) for rid, toks in sched.results.items()
                if not isinstance(rid, str)}
    by_rid = {r.rid: r for r in reqs}
    dropped = observations["moe_dropped_tokens"]

    def check(control: bool = False) -> List[harness.Check]:
        nonlocal sched, params
        sched.session.step = inner_step
        del sched, params
        gc.collect()
        return compare(run, cfg, weights, by_rid, finished, dropped,
                       control)

    return harness.Measured(metrics=metrics, attempted=len(window_due),
                            failed=failed, observations=observations,
                            check=check, report=counters["memory"])


def compare(run, cfg, weights, by_rid, finished, dropped: int,
            control: bool = False) -> List[harness.Check]:
    """``drivers/serve.py``'s comparison against the DeepSeek-V2
    reference, with the MoE's dropped choices (dropless serving drops
    none) as a check of its own."""
    spec = run.spec
    lim = spec["limits"]
    wrong_len = sum(len(t) != by_rid[r].max_new for r, t in finished.items())
    V = cfg["vocab_size"]
    out_of_vocab = sum(int(((t < 0) | (t >= V)).sum())
                       for t in finished.values())
    picked = serve.sample_for_check(by_rid, finished, run.seed,
                                    spec["check_tokens"])
    drawn = serve.sample_for_check(by_rid, finished, run.seed,
                                   spec["check_sampled_tokens"],
                                   greedy=False)
    t0 = time.perf_counter()
    n_tok = sum(len(finished[r]) for r in picked)
    n_drawn = sum(len(finished[r]) for r in drawn)

    def gap(rids, ctl=None):
        worst = 0.0
        for rid in rids:
            g = ref.served_gaps(cfg, weights, by_rid[rid].prompt,
                                finished[rid], ctl)
            worst = max(worst, float(g.max()))
        return worst

    total = var = 0.0
    for rid in drawn:
        e, v = ref.sampled_surprise(cfg, weights, by_rid[rid].prompt,
                                    finished[rid], by_rid[rid].temperature)
        total += float(e.sum())
        var += float(v.sum())
    z = abs(total) / max(var, 1e-30) ** 0.5 if drawn else float("inf")
    checks = [harness.Check("served_logit_gap",
                            gap(picked) if picked else float("inf"),
                            lim["served_logit_gap"]),
              harness.Check("sampled_nll_z", z, lim["sampled_nll_z"]),
              harness.Check("length_errors", float(wrong_len + out_of_vocab),
                            0.0),
              harness.Check("moe_dropped_tokens", float(dropped), 0.0)]
    if control and picked:
        checks.append(harness.Check("control_served_logit_gap",
                                    gap(picked, ref.quantized(weights)),
                                    lim["served_logit_gap"]))
    harness.log(f"reference: {len(picked)} greedy requests, {n_tok} served "
                f"tokens; {len(drawn)} sampled requests, {n_drawn} served "
                f"tokens; {time.perf_counter() - t0:.1f} s")
    return checks
