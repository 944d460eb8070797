"""Serving launcher: continuous-batching inference over tournament winners.

Serves the models ``launch/ltfb.py`` trains.  Two workloads behind one
CLI:

  * **lm** (any registered LM arch) — a mixed-length synthetic request
    trace through the continuous-batching scheduler
    (:mod:`repro.serve.scheduler`): token-budget admission, slot-based
    prefill/decode interleave, per-request completion.
  * **surrogate** (``--arch icf-cyclegan``) — batched ICF-surrogate
    queries through :mod:`repro.serve.surrogate`.

With ``--ckpt-dir`` pointing at an LTFB population checkpoint the
launcher serves the tournament winner (exporting ``winner_step_<n>.ckpt``
if needed) and, with ``--watch-every N``, hot-swaps newer winners
between scheduler steps — serving follows training live.

With ``--gateway`` the synthetic trace is replaced by the HTTP front
door (:mod:`repro.serve.gateway`): requests arrive over ``POST
/v1/generate``, admission is bounded by ``--max-queue`` (429 on
overload), and tokens stream back as NDJSON chunks.

Fault tolerance: ``--journal`` appends every admitted request and
every decoded token to a write-ahead journal
(:mod:`repro.serve.journal`); after a crash (or a SIGTERM-driven
rolling restart of the gateway) the next generation passes
``--resume-journal`` and resumes every unfinished request
**token-identically**.  ``--fault-spec`` arms the deterministic
fault-injection harness (:mod:`repro.serve.faults`) for crash drills.

  python -m repro.launch.serve --arch qwen3-0.6b --smoke --requests 8
  python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --ckpt-dir /tmp/pop --watch-every 4
  python -m repro.launch.serve --arch icf-cyclegan --smoke --queries 32
  python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --gateway --port 8000 --max-queue 64
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.configs.registry import ARCHS, get_config
from repro.data.tokens import token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import telemetry as telemetry_mod
from repro.serve.registry import ModelRegistry
from repro.serve.scheduler import Request, Scheduler


def parse_lens(spec: str) -> List[int]:
    """Parse a comma-separated prompt-length list ("8,16,24")."""
    return [int(x) for x in spec.split(",") if x]


def build_requests(cfg, requests: int, prompt_lens: List[int],
                   max_new: int, eos_id: Optional[int] = None,
                   temperature: float = 0.0, seed: int = 0
                   ) -> List[Request]:
    """Deterministic mixed-length trace: prompt lengths cycle through
    `prompt_lens`, token ids from the synthetic stream."""
    lens = list(prompt_lens)
    stream = token_stream(sum(lens[i % len(lens)] for i in
                              range(requests)) + requests,
                          cfg.vocab_size, seed=seed)
    reqs, off = [], 0
    for i in range(requests):
        n = lens[i % len(lens)]
        reqs.append(Request(
            rid=i, prompt=np.asarray(stream[off:off + n], np.int32),
            max_new=max_new, eos_id=eos_id, temperature=temperature,
            seed=None if temperature <= 0 else seed + i))
        off += n
    return reqs


def make_registry(args, like_params, metric_fn=None,
                  val_batch=None) -> Optional[ModelRegistry]:
    if not args.ckpt_dir:
        return None
    return ModelRegistry(args.ckpt_dir, like_params, metric_fn=metric_fn,
                         val_batch=val_batch, auto_export=True)


def make_arena(args, cfg, like, rank: int = 0):
    """Build the online-LTFB arena from the CLI flags (None when
    ``--arena`` was not given).  With ``--resume-journal`` the arena
    state (champion, windows, generation) is restored from the journal
    BEFORE the scheduler is built, so the resumed process serves the
    journaled champion from its first step."""
    if not getattr(args, "arena", None):
        return None
    from repro.serve.arena import Arena, ArenaConfig
    acfg = ArenaConfig(policy=args.arena_policy,
                       window=args.arena_window,
                       min_samples=args.arena_min_samples,
                       margin=args.arena_margin,
                       hysteresis=args.arena_hysteresis,
                       check_every=args.arena_check_every,
                       seq_len=args.arena_seq)
    arena = Arena.from_population(
        args.arena, like, acfg,
        writeback_dir=getattr(args, "arena_writeback", None),
        vocab=cfg.vocab_size, rank=rank)
    if getattr(args, "resume_journal", None):
        from repro.serve import journal as journal_mod
        state = journal_mod.replay_arena(args.resume_journal)
        if state:
            arena.restore(state)
            print(f"[serve] arena: restored from journal — champion="
                  f"{arena.champion} generation={arena.generation} "
                  f"promotions={arena.promotions}")
    print(f"[serve] arena: {args.arena} policy={acfg.policy} "
          f"members={len(arena.members)} champion={arena.champion} "
          f"drafter={arena.active_drafter} window={acfg.window} "
          f"margin={acfg.margin} min_samples={acfg.min_samples} "
          f"hysteresis={acfg.hysteresis} "
          f"writeback={getattr(args, 'arena_writeback', None)}")
    return arena


def run_lm(args) -> Dict[str, object]:
    from repro.models.lm import init_lm
    from repro.serve.registry import check_draft_compat, load_draft

    cfg = get_config(args.arch, smoke=args.smoke)
    like, _ = init_lm(cfg, jax.random.PRNGKey(args.seed))
    params = like
    if args.arena and (args.ckpt_dir or args.draft_ckpt):
        raise SystemExit(
            "--arena replaces both --ckpt-dir (promotions ARE the hot "
            "swap) and --draft-ckpt (challengers ARE the drafters); "
            "drop those flags")
    arena = make_arena(args, cfg, like)
    # the arena replaces the registry: promotions drive the hot swap
    registry = make_registry(args, like) if arena is None else None
    if registry is not None:
        params = registry.load()
        print(f"[serve] winner: step={registry.step} "
              f"trainer={registry.info.get('trainer')} "
              f"wins={registry.info.get('wins')}")
    draft_params, draft_cfg = None, None
    if args.draft_ckpt:
        draft_like = like
        if args.draft_arch and args.draft_arch != args.arch:
            # a SMALLER draft arch: its own config + param template,
            # tokenizer-compat asserted before any restore is attempted
            draft_cfg = get_config(args.draft_arch, smoke=args.smoke)
            check_draft_compat(cfg, draft_cfg)
            draft_like, _ = init_lm(draft_cfg,
                                    jax.random.PRNGKey(args.seed))
        draft_params, dinfo = load_draft(args.draft_ckpt, draft_like,
                                         step=args.draft_step,
                                         expect_vocab=cfg.vocab_size)
        print(f"[serve] drafter: {args.draft_ckpt} "
              f"arch={(draft_cfg or cfg).name} "
              f"step={dinfo.get('step')} trainer={dinfo.get('trainer')} "
              f"spec_tokens={args.spec_tokens} "
              f"fused={not args.no_spec_fused} adapt={args.spec_adapt}")
    if arena is not None:
        params = arena.champion_params
        draft_params = arena.drafter_params
    journal = None
    if getattr(args, "journal", None):
        from repro.serve.journal import RequestJournal
        journal = RequestJournal(args.journal)
        print(f"[serve] journal: {args.journal} (write-ahead, fsync "
              f"per step)")
    faults = None
    if getattr(args, "fault_spec", None):
        from repro.serve.faults import FaultInjector
        faults = FaultInjector(args.fault_spec)
        print(f"[serve] fault harness armed: {args.fault_spec}")
    max_len = args.max_len or max(
        parse_lens(args.prompt_lens)) + args.max_new
    sched_kw = dict(
        num_slots=args.slots, max_len=max_len,
        block_size=args.block_size, num_blocks=args.num_blocks,
        max_seq=args.max_seq, layout=args.layout,
        policy=args.policy, prefill_chunk=args.prefill_chunk,
        prefix_sharing=not args.no_prefix_sharing,
        pin_prefix=args.pin_prefix,
        max_prefills_per_step=args.prefill_per_step,
        registry=registry, watch_every=args.watch_every,
        swap_mode=args.swap_mode,
        draft_params=draft_params, spec_tokens=args.spec_tokens,
        draft_cfg=draft_cfg, spec_fused=not args.no_spec_fused,
        spec_adapt=args.spec_adapt,
        max_queue=getattr(args, "max_queue", None),
        journal=journal, faults=faults, arena=arena,
        telemetry=not args.no_telemetry)
    if args.mesh:
        from repro.serve.mesh import MeshScheduler, parse_mesh
        data, model = parse_mesh(args.mesh)
        sched = MeshScheduler(cfg, params, mesh_shape=(data, model),
                              **sched_kw)
        print(f"[serve] mesh: data={data} model={model} "
              f"devices={data * model} slots={sched.pool.num_slots} "
              f"(host-0 scheduler, per-shard page pools)")
    else:
        sched = Scheduler(cfg, params, **sched_kw)
    if args.profile_steps > 0:
        sched.profile_steps(args.profile_steps, args.profile_dir)
        print(f"[serve] profiler armed: steps={args.profile_steps} "
              f"dir={args.profile_dir}")
    prefixes: Dict = {}
    resumed: set = set()
    journal_entries = None
    if getattr(args, "resume_journal", None):
        from repro.serve import journal as journal_mod
        journal_entries = journal_mod.replay(args.resume_journal)
        prefixes = journal_mod.resume_scheduler(sched, journal_entries)
        resumed = set(journal_entries)
        print(f"[serve] journal: replayed {len(journal_entries)} "
              f"request(s) from {args.resume_journal} "
              f"(requeued {sched.stats.journal_replayed} unfinished)")
    if getattr(args, "gateway", False):
        out = run_gateway(args, sched, journal_entries=journal_entries)
        _maybe_write_trace(args, sched)
        if arena is not None:
            arena.report()
            arena.close()
            out["arena"] = arena.snapshot()
        if journal is not None:
            journal.close()
        return out
    reqs = build_requests(cfg, args.requests, parse_lens(args.prompt_lens),
                          args.max_new, eos_id=args.eos_id,
                          temperature=args.temperature, seed=args.seed)
    print(f"[serve] arch={cfg.name} workload=lm layout={args.layout} "
          f"policy={args.policy} slots={args.slots} max_len={max_len} "
          f"max_seq={sched.max_seq} block_size={args.block_size} "
          f"prefill_chunk={args.prefill_chunk} "
          f"swap_mode={args.swap_mode} requests={len(reqs)} "
          f"max_new={args.max_new} spec_tokens={sched.spec_tokens}")
    for r in reqs:
        if r.rid in resumed:        # the journal already owns this rid
            continue
        try:
            sched.submit(r)
        except ValueError as e:     # counted in the rejected stat
            print(f"[serve] rejected request {r.rid}: {e}")
    results = sched.run()
    if prefixes:
        from repro.serve import journal as journal_mod
        results = journal_mod.stitched_results(results, prefixes)
    sched.stats.report()
    pd = sched.pool.as_dict()
    print(f"[serve] pool: slots={pd['num_slots']} "
          f"blocks_used_high_water={pd['high_water_blocks']}/"
          f"{pd['num_blocks']} block_allocs={pd['block_allocs']} "
          f"block_frees={pd['block_frees']}")
    if args.layout == "paged":
        print(f"[serve] prefix-cache: hits={pd['prefix_hits']} "
              f"shared_tokens={pd['prefix_shared_tokens']} "
              f"pinned={pd['pinned_blocks']} "
              f"prefill_chunks={sched.stats.prefill_chunks}")
    if args.spec_adapt and sched.spec_k_by_rid:
        ks = sched.spec_k_by_rid
        print(f"[serve] spec-adapt per-row K (final): "
              f"{ {r: ks[r] for r in sorted(ks, key=str)} } "
              f"k_mean={sched.stats.as_dict()['spec_k_mean']:.2f}")
    if registry is not None:
        print(f"[serve] registry: serving_step={registry.step} "
              f"hot_swaps={sched.stats.hot_swaps}")
    if arena is not None:
        arena.report()
        arena.close()
    sample = results.get(reqs[0].rid)
    if sample is None and results:
        sample = next(iter(results.values()))
    if sample is not None:
        print("[serve] sample continuation (token ids):",
              list(map(int, sample[:12])))
    _maybe_write_trace(args, sched)
    if journal is not None:
        journal.close()
    out = {"stats": sched.stats.as_dict(), "pool": pd,
           "registry_step": registry.step if registry else None,
           "results": results, "scheduler": sched}
    if arena is not None:
        out["arena"] = arena.snapshot()
    _maybe_write_json(args, out)
    return out


def _maybe_write_json(args, out: Dict[str, object]) -> None:
    """Write the stats + full per-request token streams as JSON if
    ``--out-json`` was given (the crash-recovery CI lane diffs these
    files across an interrupted-then-resumed pair of runs)."""
    if not getattr(args, "out_json", None):
        return
    payload = {"stats": out["stats"],
               "results": {str(k): [int(t) for t in v]
                           for k, v in out.get("results", {}).items()}}
    if out.get("arena") is not None:
        payload["arena"] = out["arena"]
    with open(args.out_json, "w") as f:
        json.dump(payload, f)
    print(f"[serve] wrote {args.out_json}")


def _maybe_write_trace(args, sched) -> None:
    """Export the Chrome-trace ring buffer if --trace-out was given."""
    if not getattr(args, "trace_out", None):
        return
    telemetry_mod.write_trace(sched.telemetry.tracer, args.trace_out)
    tr = sched.telemetry.tracer
    print(f"[serve] trace: {args.trace_out} events={len(tr.events)} "
          f"dropped={tr.dropped} (chrome://tracing / ui.perfetto.dev)")


def run_gateway(args, sched, journal_entries=None) -> Dict[str, object]:
    """Serve HTTP on ``--host:--port`` until interrupted.

    Ctrl-C prints the ``[serve]`` report and exits cleanly.  SIGTERM
    triggers the graceful rolling-restart path: stop admission
    (:meth:`Gateway.begin_drain`), let in-flight work finish for up to
    ``--drain-grace`` seconds (a ``--journal`` makes the queue durable
    so the wait can be short), then exit 0 — the next generation
    resumes with ``--resume-journal``."""
    import asyncio

    from repro.serve.gateway import Gateway

    gw = Gateway(sched, host=args.host, port=args.port,
                 stream_buffer=args.stream_buffer)
    if journal_entries:
        from repro.serve.journal import idempotency_map
        gw.seed_idempotency(idempotency_map(journal_entries))

    async def _serve():
        await gw.start()
        print(f"[serve] gateway: http://{gw.host}:{gw.port} "
              f"max_queue={sched.max_queue} "
              f"stream_buffer={gw.stream_buffer} "
              f"(POST /v1/generate, GET /healthz, GET /readyz, "
              f"GET /metrics, GET /population, POST /arena/promote, "
              f"GET /debug/trace, POST /debug/profile)")
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()

        def _on_sigterm():
            print(f"[serve] SIGTERM: draining "
                  f"(grace={args.drain_grace:.1f}s, journal="
                  f"{'on' if sched.journal is not None else 'off'})",
                  flush=True)
            gw.begin_drain()
            stop.set()

        try:
            loop.add_signal_handler(signal.SIGTERM, _on_sigterm)
        except (NotImplementedError, ValueError, RuntimeError):
            pass                      # non-main thread / exotic loop
        await stop.wait()
        # with a journal the queue is already durable; either way give
        # in-flight requests up to --drain-grace to finish streaming
        deadline = loop.time() + args.drain_grace
        while not gw.drained() and loop.time() < deadline:
            await asyncio.sleep(0.05)
        if sched.journal is not None:
            sched.journal.record_note("shutdown", drained=gw.drained())
        await gw.stop()

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass
    sched.stats.report()
    return {"stats": sched.stats.as_dict()}


def run_surrogate(args) -> Dict[str, object]:
    from repro.configs.icf_cyclegan import FULL, SMOKE
    from repro.data import jag
    from repro.models.icf_cyclegan import init_cyclegan
    from repro.serve.surrogate import SurrogateEngine

    ccfg = SMOKE if args.smoke else FULL
    params, _ = init_cyclegan(ccfg, jax.random.PRNGKey(args.seed))
    registry = make_registry(args, params)
    if registry is not None:
        params = registry.load()
        print(f"[serve] winner: step={registry.step} "
              f"trainer={registry.info.get('trainer')} "
              f"wins={registry.info.get('wins')}")
    eng = SurrogateEngine(ccfg, params, max_batch=args.slots * 16,
                          bucket=8, registry=registry,
                          watch_every=args.watch_every,
                          telemetry=not args.no_telemetry)
    print(f"[serve] arch={ccfg.name} workload=surrogate "
          f"queries={args.queries} query_batch={args.query_batch} "
          f"max_batch={eng.max_batch}")
    xs = jag.sample_inputs(args.queries * args.query_batch, args.seed)
    for i in range(args.queries):
        eng.submit(i, xs[i * args.query_batch:(i + 1) * args.query_batch])
    results = eng.run()
    eng.stats.report()
    if registry is not None:
        print(f"[serve] registry: serving_step={registry.step} "
              f"hot_swaps={eng.stats.hot_swaps}")
    _maybe_write_trace(args, eng)
    return {"stats": eng.stats.as_dict(),
            "registry_step": registry.step if registry else None,
            "results": results}


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's argument parser (separate from :func:`main` so
    ``docs/flags.md`` can be checked against it)."""
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description="Continuous-batching inference over tournament "
                    "winners")
    ap.add_argument("--arch", default="qwen3-0.6b", choices=sorted(ARCHS))
    ap.add_argument("--workload", default=None,
                    choices=("lm", "surrogate"),
                    help="default: surrogate for icf-cyclegan, else lm")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="LTFB population checkpoint dir to serve the "
                         "tournament winner from")
    ap.add_argument("--watch-every", type=int, default=0,
                    help="poll for newer winners every N steps (0 = off)")
    ap.add_argument("--mesh", default=None,
                    help="serve over a device mesh: 'DATA,MODEL' (e.g. "
                         "'4,2') or 'data=4,model=2' — weights "
                         "tensor-parallel over `model`, decode batch + "
                         "every cache leaf (incl. per-shard page pools) "
                         "over `data`, admission decided on host 0 and "
                         "broadcast (lm workload)")
    # scheduler
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=0,
                    help="default per-request cap + pool sizing unit "
                         "(0 = fit the trace)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size in tokens")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="page-pool size (default: slots*max_len worth)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="per-request length cap (paged layout; default "
                         "max_len — raise it to admit requests longer "
                         "than the old dense per-slot ceiling)")
    ap.add_argument("--layout", default="paged",
                    choices=("paged", "dense"),
                    help="paged: scattered KV pages + gather-decode "
                         "kernel; dense: PR-2 slot rows (baseline)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill long prompts in N-token chunks "
                         "interleaved with decode (0 = one-shot; "
                         "attention-only families)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-admit prompt prefix sharing")
    ap.add_argument("--pin-prefix", action="store_true",
                    help="keep registered prompt-prefix pages resident "
                         "across idle periods (eviction-priority tier; "
                         "reclaimed oldest-first under pool pressure)")
    # speculative decoding (population drafter)
    ap.add_argument("--draft-ckpt", default=None,
                    help="drafter checkpoint for speculative decoding: "
                         "a .ckpt file, or a population dir (earliest "
                         "step's winner by default) — the LTFB "
                         "population is a free source of draft models")
    ap.add_argument("--draft-step", type=int, default=None,
                    help="population step to draft from (with a dir "
                         "--draft-ckpt; default: earliest)")
    ap.add_argument("--draft-arch", default=None, choices=sorted(ARCHS),
                    help="the drafter's arch when it differs from the "
                         "target (a smaller model; must share the "
                         "target's vocab/tokenizer — checked at load)")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="draft tokens proposed per speculative round "
                         "(0 = off); the target verifies K+1 tokens in "
                         "one multi-token step — output is token-"
                         "identical to target-only decoding")
    ap.add_argument("--no-spec-fused", action="store_true",
                    help="disable the fused draft step (K proposals in "
                         "ONE dispatch via on-device greedy feed + a "
                         "redraw by the device sampler; off = K+1 "
                         "sequential draft dispatches per round)")
    ap.add_argument("--spec-adapt", action="store_true",
                    help="adapt the speculative depth PER ROW from its "
                         "accept-rate history (within [1, spec-tokens]); "
                         "per-row K reported in the [serve] metrics")
    # online LTFB arena (serve/arena.py: live-traffic tournament)
    ap.add_argument("--arena", default=None,
                    help="serve an N-member population roster from this "
                         "LTFB checkpoint dir as an ONLINE tournament: "
                         "the champion serves, challengers draft "
                         "speculatively, accept rate scores matches, "
                         "and winners are hot-swapped in (replaces "
                         "--ckpt-dir and --draft-ckpt; lm workload)")
    ap.add_argument("--arena-policy", default="champion",
                    choices=("champion", "epsilon", "shadow"),
                    help="challenger routing: champion = best "
                         "challenger drafts (exploit); epsilon = mostly "
                         "best, periodically round-robin (explore/"
                         "exploit); shadow = round-robin every stint "
                         "(even sampling)")
    ap.add_argument("--arena-window", type=int, default=128,
                    help="sliding accept-rate window per member, in "
                         "speculative row-rounds (the match metric)")
    ap.add_argument("--arena-margin", type=float, default=0.02,
                    help="a challenger must beat the champion's "
                         "promotion-time accept rate by this margin to "
                         "win a match")
    ap.add_argument("--arena-min-samples", type=int, default=32,
                    help="proposals a challenger's window must hold "
                         "before it can qualify for promotion")
    ap.add_argument("--arena-hysteresis", type=int, default=2,
                    help="consecutive winning match evaluations before "
                         "a promotion fires")
    ap.add_argument("--arena-check-every", type=int, default=8,
                    help="scheduler steps between match evaluations")
    ap.add_argument("--arena-writeback", default=None,
                    help="write finished request/response streams back "
                         "as datastore token shards in this dir — the "
                         "next launch/ltfb.py round ingests production "
                         "traffic (train->serve->train)")
    ap.add_argument("--arena-seq", type=int, default=64,
                    help="write-back row width minus one: rows are "
                         "(seq+1) tokens, matching launch/ltfb.py "
                         "--seq so shards re-ingest directly")
    ap.add_argument("--swap-mode", default="immediate",
                    choices=("immediate", "drain"),
                    help="hot-swap policy: immediate applies new "
                         "weights to in-flight requests; drain lets "
                         "them finish on the old weights first")
    ap.add_argument("--policy", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--prefill-per-step", type=int, default=1)
    # lm trace
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-lens", default="8,16,24",
                    help="comma list; requests cycle through these")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    # surrogate trace
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--query-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # gateway (HTTP front door)
    ap.add_argument("--gateway", action="store_true",
                    help="serve HTTP (POST /v1/generate, GET /healthz, "
                         "GET /metrics) instead of the synthetic trace "
                         "(lm workload)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="gateway bind address")
    ap.add_argument("--port", type=int, default=8000,
                    help="gateway bind port (0 = ephemeral)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the request queue; submits beyond it "
                         "are shed with HTTP 429 (default: unbounded)")
    ap.add_argument("--stream-buffer", type=int, default=64,
                    help="per-response token buffer; a consumer that "
                         "falls further behind is cancelled "
                         "(backpressure)")
    # fault tolerance (journal / crash recovery / fault injection)
    ap.add_argument("--journal", default=None,
                    help="write-ahead request journal (JSONL): every "
                         "admitted request and decoded token, fsync'd "
                         "per scheduler step — a crashed or restarted "
                         "server resumes from it token-identically "
                         "(lm workload)")
    ap.add_argument("--resume-journal", default=None,
                    help="replay a previous generation's --journal on "
                         "startup: finished requests return their "
                         "recorded tokens, unfinished ones are "
                         "requeued and resume token-identically")
    ap.add_argument("--fault-spec", default=None,
                    help="deterministic fault injection: comma list of "
                         "kind@step[:key=val...], kinds kill|crash|"
                         "stall|corrupt|oom|disconnect (e.g. "
                         "'kill@12,stall@4:secs=0.2') — crash drills "
                         "for the journal/recovery path")
    ap.add_argument("--out-json", default=None,
                    help="write final stats + per-request token "
                         "streams as JSON (the crash-recovery CI lane "
                         "diffs interrupted-vs-uninterrupted runs)")
    ap.add_argument("--drain-grace", type=float, default=5.0,
                    help="seconds SIGTERM waits for in-flight gateway "
                         "requests to finish before exiting (admission "
                         "stops immediately; the journal preserves "
                         "whatever does not finish)")
    # telemetry (tracing / metrics / profiler)
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable per-request trace spans and phase "
                         "spans (counters, histograms and the profiler "
                         "window stay on)")
    ap.add_argument("--trace-out", default=None,
                    help="write the per-request trace ring buffer as "
                         "Chrome-trace JSON on exit (open in "
                         "chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--profile-steps", type=int, default=0,
                    help="wrap jax.profiler.trace around the first N "
                         "scheduler steps (0 = off; lm workload)")
    ap.add_argument("--profile-dir", default="/tmp/repro_profile",
                    help="output dir for --profile-steps / POST "
                         "/debug/profile traces (TensorBoard-loadable)")
    ap.add_argument("--log-json", action="store_true",
                    help="emit [serve] reports and lifecycle events "
                         "(shed/cancel/hot-swap/profile) as one-line "
                         "JSON records on stdout")
    return ap


def main(argv=None) -> int:
    """CLI entry point: parse args, pick the workload, run it."""
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.log_json:
        telemetry_mod.enable_json_logs()
    if (args.draft_ckpt or args.arena) and args.spec_tokens <= 0:
        args.spec_tokens = 4            # a drafter implies speculation
    workload = args.workload or \
        ("surrogate" if args.arch == "icf-cyclegan" else "lm")
    if workload == "surrogate":
        run_surrogate(args)
    else:
        if args.arch == "icf-cyclegan":
            raise SystemExit("lm workload needs an LM arch")
        run_lm(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
