"""Multi-process mesh serving harness (``jax.distributed``).

Runs the :class:`repro.serve.mesh.MeshScheduler` across REAL OS
processes: host 0 decides (admission, hot swap, submits, cancels) and
broadcasts a :class:`repro.serve.mesh.StepPlan` every step; followers
replay it and land in an identical state.  On CPU the plan rides the
jax coordination service (XLA's CPU backend cannot run cross-process
computations), each process holds a full model replica on its private
local mesh, and a per-step barrier turns a dead peer into a clean
timeout error instead of a hang.  On TPU/GPU the same harness gets a
true global mesh and device-collective broadcasts.

Two modes in one CLI:

* **spawn** (``--procs N``): pick a free coordinator port, launch N
  worker copies of this module, supervise them (first failure kills
  the rest), exit with the workers' status.  The workers run on the
  CPU, and the spawner refuses to start unless ``JAX_PLATFORMS=cpu``
  is set: on an accelerator host each worker would open every chip;
* **worker** (``--process-id I --num-processes N --coordinator
  HOST:PORT``): ``jax.distributed.initialize``, build the scheduler,
  run the request trace (host 0) or the replay loop (followers), and
  write per-process results to ``--out-json`` (followers append
  ``.pI``) so token identity across processes is checkable from the
  outside.

  JAX_PLATFORMS=cpu python -m repro.launch.distributed --procs 2 \
      --smoke --requests 4 --max-new 8 --out-json /tmp/dist.json
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
from typing import List, Optional

# NOTE: no top-level jax import — the spawner must be able to set
# XLA_FLAGS in the children's environment before THEIR jax import, and
# must not initialize a backend in the parent at all.


def find_free_port(host: str = "127.0.0.1") -> int:
    """Bind port 0, return the OS-assigned free port."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def build_parser() -> argparse.ArgumentParser:
    """The distributed CLI's argument parser (separate from
    :func:`main` so ``docs/flags.md`` can be checked against it)."""
    ap = argparse.ArgumentParser(
        prog="repro.launch.distributed",
        description="Multi-process mesh serving over jax.distributed")
    # spawn mode
    ap.add_argument("--procs", type=int, default=0,
                    help="spawn N worker processes and supervise them "
                         "(0 = run as a worker in THIS process)")
    # worker topology
    ap.add_argument("--process-id", type=int, default=0,
                    help="this worker's rank (0 = host-0 scheduler)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total worker count in the job")
    ap.add_argument("--coordinator", default="127.0.0.1:0",
                    help="jax.distributed coordinator HOST:PORT "
                         "(the spawner fills this in)")
    ap.add_argument("--step-timeout", type=float, default=60.0,
                    help="per-step plan-broadcast timeout in seconds; "
                         "a dead peer raises instead of hanging")
    ap.add_argument("--feed", default="host0",
                    choices=("host0", "replicated"),
                    help="host0: only host 0 sees the requests "
                         "(followers receive them in the plan, the "
                         "gateway path); replicated: every process "
                         "submits the trace locally")
    # model / trace (mirrors launch.serve)
    ap.add_argument("--arch", default="qwen3-0.6b",
                    help="registered LM arch to serve")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mesh", default="1,1",
                    help="per-process mesh 'DATA,MODEL' (CPU: each "
                         "process holds a full replica on its local "
                         "devices)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-request cap + pool sizing (0 = fit the "
                         "trace)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-lens", default="8,16",
                    help="comma list; requests cycle through these")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-json", default=None,
                    help="write {rid: tokens} + stats here (followers "
                         "append .p<rank>)")
    # fault tolerance
    ap.add_argument("--journal", default=None,
                    help="host-0 write-ahead request journal (JSONL); "
                         "restarted generations resume unfinished "
                         "requests from it token-identically")
    ap.add_argument("--resume-journal", default=None,
                    help="replay a previous generation's --journal on "
                         "host 0 before serving the trace")
    ap.add_argument("--fault-spec", default=None,
                    help="deterministic fault injection, comma list of "
                         "kind@step[:key=val...] (kinds kill|crash|"
                         "stall|corrupt|oom|disconnect; rank= picks "
                         "the victim process, default 0) — stripped "
                         "automatically on supervised restarts")
    ap.add_argument("--restart-on-failure", type=int, default=0,
                    help="spawn mode: on a worker failure, restart the "
                         "whole job up to N times with a fresh "
                         "coordinator, resuming from the previous "
                         "generation's --journal")
    # online LTFB arena (mirrors launch.serve; every rank mirrors the
    # roster, rank 0 owns the registry archive + write-back)
    ap.add_argument("--arena", default=None,
                    help="serve an N-member population roster from this "
                         "LTFB checkpoint dir as an ONLINE tournament: "
                         "champion serves, challengers draft, accept "
                         "rate scores matches, winners hot-swap in — "
                         "host 0 decides, the promotion rides the step "
                         "plan")
    ap.add_argument("--arena-policy", default="champion",
                    choices=("champion", "epsilon", "shadow"),
                    help="challenger routing: champion = best "
                         "challenger drafts (exploit); epsilon = mostly "
                         "best, periodically round-robin; shadow = "
                         "round-robin every stint (even sampling)")
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
                    help="rank 0 writes finished request/response "
                         "streams back as datastore token shards in "
                         "this dir (train->serve->train)")
    ap.add_argument("--arena-seq", type=int, default=64,
                    help="write-back row width minus one: rows are "
                         "(seq+1) tokens, matching launch/ltfb.py "
                         "--seq so shards re-ingest directly")
    return ap


def _strip_flags(argv: List[str], names) -> List[str]:
    """Drop ``--flag value`` / ``--flag=value`` pairs from an argv."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
            continue
        if a.split("=", 1)[0] in names:
            skip = "=" not in a
            continue
        out.append(a)
    return out


def spawn(args, argv: List[str]) -> int:
    """Launch ``--procs`` worker copies of this module and supervise
    them: the first nonzero exit kills the remaining workers.

    With ``--restart-on-failure N`` a failed job is relaunched up to N
    times: fresh coordinator port, ``--fault-spec`` stripped (the
    drill already fired), and — when a ``--journal`` is attached —
    the new generation resumes from the previous generation's journal
    so the combined output is token-identical to an uninterrupted
    run."""
    from repro.launch.serve import parse_lens  # no jax at import time
    from repro.serve.mesh import parse_mesh

    if os.environ.get("JAX_PLATFORMS") != "cpu":
        # every spawned worker would open every chip of this host
        raise SystemExit(
            "[dist] --procs spawns CPU workers on this host and needs "
            "JAX_PLATFORMS=cpu set explicitly; on an accelerator host "
            "start one worker per host yourself (--process-id, "
            "--num-processes, --coordinator)")
    data, model = parse_mesh(args.mesh)
    _ = parse_lens(args.prompt_lens)    # fail fast on a bad trace spec
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{data * model}").strip()
    # workers re-run this argv minus the spawn flag, plus topology
    passthrough = _strip_flags(argv, ("--procs",))
    restarts = max(0, int(getattr(args, "restart_on_failure", 0) or 0))
    journal = getattr(args, "journal", None)
    attempt = 0
    while True:
        extra: List[str] = []
        if attempt > 0:
            # the fault already fired; a restarted generation gets a
            # clean spec, a fresh journal file and the previous
            # generation's journal to resume from
            extra = _strip_flags(
                passthrough, ("--fault-spec", "--journal",
                              "--resume-journal"))
            if journal is not None:
                prev = journal if attempt == 1 \
                    else f"{journal}.r{attempt - 1}"
                extra += ["--journal", f"{journal}.r{attempt}",
                          "--resume-journal", prev]
            status = _spawn_once(args, extra, env)
        else:
            status = _spawn_once(args, passthrough, env)
        if status == 0 or attempt >= restarts:
            return status
        attempt += 1
        print(f"[dist] worker failure (rc={status}); restarting the "
              f"job (attempt {attempt}/{restarts})"
              + (f", resuming from the generation-{attempt - 1} "
                 f"journal" if journal else ""), flush=True)


def _spawn_once(args, passthrough: List[str], env: dict) -> int:
    """One supervised generation: launch the workers on a fresh
    coordinator port, return the job's exit status."""
    port = find_free_port()
    coordinator = f"127.0.0.1:{port}"
    procs = []
    for rank in range(args.procs):
        cmd = [sys.executable, "-m", "repro.launch.distributed",
               *passthrough, "--process-id", str(rank),
               "--num-processes", str(args.procs),
               "--coordinator", coordinator]
        procs.append(subprocess.Popen(cmd, env=env))
    status = 0
    try:
        live = list(procs)
        while live:
            for p in list(live):
                rc = p.poll()
                if rc is None:
                    continue
                live.remove(p)
                if rc != 0:
                    status = rc
                    # one dead worker stalls the others at the next
                    # barrier; don't wait for the timeout to prove it
                    for q in live:
                        q.terminate()
            time.sleep(0.05)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return status


def run_worker(args) -> int:
    """One worker process: initialize the process group, serve the
    trace (host 0) or replay plans (followers), write results."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    if args.num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
            initialization_timeout=max(int(args.step_timeout), 10))
    import numpy as np

    from repro.configs.registry import get_config
    from repro.launch.serve import build_requests, parse_lens
    from repro.models.lm import init_lm
    from repro.serve.mesh import MeshScheduler, parse_mesh

    cfg = get_config(args.arch, smoke=args.smoke)
    # identical seed -> identical replica weights in every process
    params, _ = init_lm(cfg, jax.random.PRNGKey(args.seed))
    lens = parse_lens(args.prompt_lens)
    max_len = args.max_len or max(lens) + args.max_new
    journal = None
    if args.journal and args.process_id == 0:
        from repro.serve.journal import RequestJournal
        journal = RequestJournal(args.journal)
    faults = None
    if args.fault_spec:
        from repro.serve.faults import FaultInjector
        faults = FaultInjector(args.fault_spec, rank=args.process_id)
    # online LTFB arena: EVERY rank mirrors the roster (promotions are
    # replayed from the plan); rank 0 alone archives + writes back.
    # All ranks replay the journaled arena state on resume (shared
    # filesystem) so the mesh starts aligned on the same champion.
    arena, spec_tokens, draft_params = None, 0, None
    if args.arena:
        from repro.launch.serve import make_arena
        arena = make_arena(args, cfg, params, rank=args.process_id)
        spec_tokens = 4
        draft_params = arena.drafter_params
    sched = MeshScheduler(
        cfg, arena.champion_params if arena is not None else params,
        mesh_shape=parse_mesh(args.mesh),
        local_mesh=args.num_processes > 1,
        step_timeout_s=args.step_timeout,
        num_slots=args.slots, max_len=max_len,
        journal=journal, faults=faults, arena=arena,
        draft_params=draft_params, spec_tokens=spec_tokens)
    rank = jax.process_index()
    print(f"[dist] rank={rank}/{args.num_processes} arch={cfg.name} "
          f"mesh={args.mesh} feed={args.feed} slots={sched.pool.num_slots} "
          f"channel={type(sched.channel).__name__}", flush=True)
    reqs = build_requests(cfg, args.requests, lens, args.max_new,
                          temperature=args.temperature, seed=args.seed)
    prefixes: dict = {}
    resumed: set = set()
    if rank == 0 and args.resume_journal:
        from repro.serve import journal as journal_mod
        entries = journal_mod.replay(args.resume_journal)
        prefixes = journal_mod.resume_scheduler(sched, entries)
        resumed = set(entries)
        print(f"[dist] rank=0 journal: replayed {len(entries)} "
              f"request(s) from {args.resume_journal} "
              f"(requeued {sched.stats.journal_replayed} unfinished)",
              flush=True)
    if rank == 0:
        for r in reqs:
            if r.rid in resumed:    # the journal already owns this rid
                continue
            sched.submit(r)
        try:
            while sched.queue or sched.active or sched.prefilling:
                sched.step()
        except RuntimeError as e:
            # confirmed peer death: make the in-flight state durable
            # before dying so the restarted generation can resume
            if journal is not None:
                journal.record_note("peer_death", error=str(e)[:200])
                journal.close()
            raise
        sched.shutdown()
        results = sched.results
        if prefixes:
            from repro.serve import journal as journal_mod
            results = journal_mod.stitched_results(results, prefixes)
    else:
        if args.feed == "replicated":
            # exercise the dedupe path: the plan's submits must be
            # recognized as already-local copies, not enqueued twice
            for r in reqs:
                sched.submit(r)
        results = sched.run_follower()
    if journal is not None:
        journal.close()
    sched.stats.stop()
    if rank == 0:
        sched.stats.report(prefix="[dist]")
        if arena is not None:
            arena.report(prefix="[dist][arena]")
    if arena is not None:
        arena.close()
    out = {"rank": rank,
           "results": {str(rid): [int(t) for t in toks]
                       for rid, toks in results.items()},
           "stats": sched.stats.as_dict()}
    if arena is not None:
        out["arena"] = arena.snapshot()
    if rank == 0:
        # the gathered per-rank snapshots — host-0's export covers the
        # whole mesh, so one scrape sees every process's counters
        out["mesh_stats"] = {str(r): s
                             for r, s in sched.remote_stats.items()}
    if args.out_json:
        path = args.out_json if rank == 0 \
            else f"{args.out_json}.p{rank}"
        with open(path, "w") as f:
            json.dump(out, f)
        print(f"[dist] rank={rank} wrote {path} "
              f"({len(results)} results)", flush=True)
        if rank == 0:
            from repro.serve import telemetry as telemetry_mod
            with open(path + ".prom", "w") as f:
                f.write(telemetry_mod.scheduler_prometheus(sched))
            print(f"[dist] rank=0 wrote {path}.prom "
                  f"(Prometheus exposition, all ranks)", flush=True)
    if args.num_processes > 1:
        jax.distributed.shutdown()
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    """CLI entry point: spawn mode with ``--procs``, else worker."""
    argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(argv)
    if args.procs > 0:
        return spawn(args, argv)
    try:
        return run_worker(args)
    except Exception:
        import traceback
        traceback.print_exc()
        # hard-exit: a failed worker must DIE, not hang in jax's
        # atexit distributed-shutdown handshake waiting for the very
        # peers it just lost — the supervisor kills the rest
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)


if __name__ == "__main__":
    sys.exit(main())
