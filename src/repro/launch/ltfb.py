"""LTFB population-training launcher (paper §III: datastore + tournament).

Runs K trainers, each fed from its own distributed-datastore partition
of an on-disk bundle manifest (JAG ICF bundles for the CycleGAN, token
shards for the LM architectures), with tournaments between rounds and
checkpoint/restart of the full population.

  python -m repro.launch.ltfb --arch icf-cyclegan --trainers 4 \
      --steps-per-round 2 --rounds 2 --smoke
  python -m repro.launch.ltfb --arch qwen3-0.6b --smoke --trainers 2
  python -m repro.launch.ltfb --arch icf-cyclegan --trainers 4 \
      --rescale-to 6 --rounds 4        # elastic rescale mid-run

Resumes from --ckpt-dir automatically unless --no-resume.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

from repro.configs.base import OptimizerConfig
from repro.configs.registry import ARCHS, get_config
from repro.core.population import TrainerFns
from repro.core.tournament import (
    DataPlan,
    TournamentConfig,
    TournamentOrchestrator,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.telemetry import (
    enable_json_logs,
    json_logs_enabled,
    log_event,
    write_trace,
)


def say(human: str, event: str, **fields):
    """Report line: one-line JSON under --log-json, human text otherwise
    (same dialect as the serve launcher's structured records)."""
    if json_logs_enabled():
        log_event(event, **fields)
    else:
        print(human)


def build_plan(args) -> DataPlan:
    """Materialize (or reuse) the on-disk bundle manifest."""
    root = args.data_dir or tempfile.mkdtemp(prefix="repro_ltfb_")
    if args.arch == "icf-cyclegan":
        from repro.data import jag
        image_size = 8 if args.smoke else 64
        files = jag.list_bundles(root)
        if files:
            got = jag.read_bundle(files[0])["images"].shape[-1]
            if got != image_size:
                raise SystemExit(
                    f"[ltfb] --data-dir {root} holds bundles at image size "
                    f"{got}, this run needs {image_size} — use a fresh "
                    "--data-dir")
        else:
            files = jag.write_bundles(root, args.samples,
                                      args.samples_per_file,
                                      image_size=image_size, seed=args.seed)
        say(f"[ltfb] manifest: {len(files)} JAG bundles in {root}",
            "ltfb_manifest", files=len(files), root=root, kind="jag")
        return DataPlan.jag_cyclegan(files)
    from repro.data import tokens
    cfg = get_config(args.arch, smoke=args.smoke)
    files = tokens.list_token_shards(root)
    if files:
        probe = tokens.read_token_shard(files[0])["tokens"]
        if probe.shape[1] != args.seq + 1 or probe.max() >= cfg.vocab_size:
            raise SystemExit(
                f"[ltfb] --data-dir {root} holds shards of seq "
                f"{probe.shape[1] - 1} / max token {probe.max()}, this run "
                f"needs seq {args.seq} / vocab {cfg.vocab_size} — use a "
                "fresh --data-dir")
    else:
        files = tokens.write_token_shards(
            root, args.samples, seq_len=args.seq, vocab=cfg.vocab_size,
            samples_per_file=args.samples_per_file, seed=args.seed)
    say(f"[ltfb] manifest: {len(files)} token shards in {root}",
        "ltfb_manifest", files=len(files), root=root, kind="tokens")
    return DataPlan.lm_tokens(files)


def build_fns(args) -> TrainerFns:
    opt = OptimizerConfig(name=args.optimizer, lr=args.lr, warmup_steps=1)
    if args.arch == "icf-cyclegan":
        from repro.configs.icf_cyclegan import FULL, SMOKE
        from repro.train.steps import make_gan_steps
        return TrainerFns(*make_gan_steps(SMOKE if args.smoke else FULL,
                                          opt))
    from repro.train.steps import make_lm_population_fns
    cfg = get_config(args.arch, smoke=args.smoke)
    return TrainerFns(*make_lm_population_fns(cfg, opt))


def report(orch: TournamentOrchestrator):
    st = orch.stats()
    for i, d in enumerate(st["per_trainer"]):
        say(f"[ltfb] trainer {i}: files={d['files']} "
            f"cache_hits={d['cache_hits']} "
            f"cache_misses={d['cache_misses']} "
            f"file_opens={d['file_opens']} "
            f"exchange_MB={d['exchange_bytes'] / 1e6:.2f} "
            f"wins={d['wins']} adoptions={d['adoptions']} "
            f"steps={d['steps']} "
            f"data_wait_s={d['data_wait_seconds']:.2f}",
            "ltfb_trainer_stats", trainer=i, **d)
    tot = st["total"]
    say(f"[ltfb] datastore total: read_MB={tot['bytes_read'] / 1e6:.2f} "
        f"exchange_MB={tot['exchange_bytes'] / 1e6:.2f} "
        f"cache_hits={int(tot['cache_hits'])} "
        f"cache_misses={int(tot['cache_misses'])} "
        f"samples={int(tot.get('samples_fetched', 0))} "
        f"prefetch_wait_s={st['prefetch_wait_seconds']:.2f}",
        "ltfb_datastore_stats",
        prefetch_wait_seconds=st["prefetch_wait_seconds"], **tot)
    wins = [d["wins"] for d in st["per_trainer"]]
    say(f"[ltfb] tournament: rounds={st['round']} win_counts={wins} "
        f"model_exchange_MB="
        f"{st['tournament_exchange_bytes'] / 1e6:.2f} "
        f"tournament_s={st['tournament_seconds']:.2f} "
        f"ckpt_s={st['checkpoint_seconds']:.2f}",
        "ltfb_tournament_stats", rounds=st["round"], win_counts=wins,
        tournament_exchange_bytes=st["tournament_exchange_bytes"],
        tournament_seconds=st["tournament_seconds"],
        checkpoint_seconds=st["checkpoint_seconds"],
        restore_seconds=st["restore_seconds"], events=st["events"])
    eff = st.get("efficiency") or {}
    if eff.get("speedup") is not None:
        say(f"[ltfb] efficiency: speedup={eff['speedup']:.2f}x "
            f"efficiency={eff['efficiency'] * 100:.0f}% "
            f"parallel_samples_per_s="
            f"{eff['parallel_samples_per_s']:.0f}",
            "ltfb_efficiency", **eff)


def build_parser() -> argparse.ArgumentParser:
    """The ltfb CLI's argument parser (separate from :func:`main` so
    ``docs/flags.md`` can be checked against it)."""
    ap = argparse.ArgumentParser(
        prog="repro.launch.ltfb",
        description="LTFB tournament training over the distributed "
                    "datastore")
    ap.add_argument("--arch", default="icf-cyclegan", choices=sorted(ARCHS))
    ap.add_argument("--trainers", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--steps-per-round", type=int, default=25)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--backend", default="host", choices=("host", "mesh"))
    ap.add_argument("--scope", default=None,
                    help="exchange scope (default: generator for GANs, "
                         "full otherwise)")
    ap.add_argument("--store-mode", default="preload",
                    choices=("preload", "dynamic", "none"))
    ap.add_argument("--num-ranks", type=int, default=2,
                    help="simulated datastore ranks per trainer")
    ap.add_argument("--partition", default="stride",
                    choices=("stride", "block"))
    ap.add_argument("--quantize-exchange", action="store_true",
                    help="int8 model exchange on the mesh backend")
    ap.add_argument("--no-async-eval", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + dataset (CPU-runnable)")
    ap.add_argument("--samples", type=int, default=None)
    ap.add_argument("--samples-per-file", type=int, default=None)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--data-dir", default=None,
                    help="bundle manifest dir (default: fresh tempdir)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint every N rounds (0 = never)")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--rescale-to", type=int, default=0,
                    help="elastically rescale to K' trainers mid-run")
    ap.add_argument("--seed", type=int, default=0)
    # observability (docs/observability.md "Training telemetry")
    ap.add_argument("--log-json", action="store_true",
                    help="one-line JSON log records instead of human text")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON of per-trainer "
                         "step/exchange/eval spans here on exit")
    ap.add_argument("--prom-out", default=None,
                    help="write a Prometheus text snapshot here each round")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the Prometheus snapshot on this HTTP "
                         "port (0 = ephemeral)")
    ap.add_argument("--genealogy", default=None,
                    help="tournament genealogy JSONL (default: "
                         "<ckpt-dir>/genealogy.jsonl when --ckpt-dir is "
                         "set; see repro.launch.lineage)")
    return ap


def run(argv=None) -> TournamentOrchestrator:
    """Parse ``argv`` and run the LTFB tournament.

    Returns the closed orchestrator: its population (params, wins,
    adoptions) and :meth:`~TournamentOrchestrator.stats` stay readable.
    """
    args = build_parser().parse_args(argv)

    if args.log_json:
        enable_json_logs()
    if args.samples is None:
        args.samples = 1024 if args.smoke else 16_384
    if args.samples_per_file is None:
        args.samples_per_file = 64 if args.smoke else 512
    rounded = (args.samples // args.samples_per_file) * args.samples_per_file
    if rounded != args.samples:
        say(f"[ltfb] rounding --samples {args.samples} -> {rounded} "
            "(datastore bundles must be uniform)",
            "ltfb_samples_rounded", requested=args.samples, used=rounded)
        args.samples = max(rounded, args.samples_per_file)
    scope = args.scope or \
        ("generator" if args.arch == "icf-cyclegan" else "full")

    plan = build_plan(args)
    fns = build_fns(args)
    cfg = TournamentConfig(
        trainers=args.trainers, scope=scope, backend=args.backend,
        store_mode=args.store_mode, num_ranks=args.num_ranks,
        partition=args.partition, batch_size=args.batch,
        tournament_batch_size=min(args.batch * 2, args.samples_per_file),
        async_eval=not args.no_async_eval,
        quantize_exchange=args.quantize_exchange,
        ckpt_dir=args.ckpt_dir, seed=args.seed)

    from repro.train.telemetry import (GenealogyLog, MetricsServer,
                                       TrainTelemetry, train_prometheus,
                                       write_prom)
    tel = TrainTelemetry() \
        if (args.trace_out or args.prom_out
            or args.metrics_port is not None) else None
    gen_path = args.genealogy or (
        os.path.join(args.ckpt_dir, "genealogy.jsonl")
        if args.ckpt_dir else None)
    gen = GenealogyLog(gen_path) if gen_path else None
    server = MetricsServer(args.metrics_port) \
        if args.metrics_port is not None else None
    if server is not None:
        say(f"[ltfb] metrics endpoint: "
            f"http://127.0.0.1:{server.port}/metrics",
            "ltfb_metrics_endpoint", port=server.port)

    orch = TournamentOrchestrator(fns, plan, cfg, telemetry=tel,
                                  genealogy=gen)
    if tel is not None or server is not None or args.prom_out:
        def on_round(o: TournamentOrchestrator):
            text = train_prometheus(
                o.stats(), tel.phase_seconds if tel else None)
            if args.prom_out:
                write_prom(text, args.prom_out)
            if server is not None:
                server.update(text)
        orch.on_round = on_round
    log_line = None if args.log_json else print
    try:
        if not args.no_resume and orch.maybe_resume():
            say(f"[ltfb] resumed at round {orch.population.round}",
                "ltfb_resumed", round=orch.population.round)
        say(f"[ltfb] arch={args.arch} K={args.trainers} "
            f"backend={args.backend} scope={scope} "
            f"store={args.store_mode}/{args.partition} "
            f"ranks={args.num_ranks}",
            "ltfb_start", arch=args.arch, trainers=args.trainers,
            backend=args.backend, scope=scope,
            store_mode=args.store_mode, partition=args.partition,
            num_ranks=args.num_ranks)
        first = args.rounds // 2 if args.rescale_to else args.rounds
        orch.run(first, args.steps_per_round,
                 ckpt_every=args.ckpt_every, log=log_line)
        if args.rescale_to:
            if not args.log_json:
                print(f"[ltfb] elastic rescale {args.trainers} -> "
                      f"{args.rescale_to}")
            orch.rescale(args.rescale_to)
            orch.run(args.rounds - first, args.steps_per_round,
                     ckpt_every=args.ckpt_every, log=log_line)
        report(orch)
        if args.trace_out and tel is not None:
            write_trace(tel.tracer, args.trace_out)
            say(f"[ltfb] wrote {args.trace_out} "
                f"(Perfetto/chrome://tracing)",
                "ltfb_trace_written", path=args.trace_out,
                events=tel.tracer.emitted,
                dropped=tel.tracer.dropped)
    finally:
        orch.close()
        if gen is not None:
            gen.close()
        if server is not None:
            server.close()
    return orch


def main(argv=None) -> int:
    """CLI entry point: parse args, run the LTFB tournament."""
    enable_compile_cache()
    run(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
