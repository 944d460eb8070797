#!/usr/bin/env python3
"""Bring-up check on a TPU: LTFB training and paged serving at full width.

  python chip_smoke.py              # one chip: phase train, phase serve
  python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Everything runs in this one process through the normal entry points:
``repro.launch.ltfb.run`` trains K=4 ICF CycleGAN trainers at the
published widths (12x64x64 images + 15 scalars, batch 128) over JAG
bundles generated from ``--seed``, and ``repro.launch.serve.run_lm``
serves qwen3-0.6b at its published widths with random weights from
``--seed`` through the paged continuous-batching scheduler.  With
``--chips 4`` only the two paths that span chips run, each against its
one-chip counterpart: the LTFB ``mesh`` tournament backend against
``host``, and serving on a 2x2 ("data", "model") mesh against one chip.

Generated data and the run's files go under ``.chip_smoke/`` in the
checkout.  Earlier lines report compile seconds, step and request
times (taken after the work is ready on the device), peak device
memory and where the trainers' params live.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  With no TPU, or outside a
checkout of the repository, the script exits nonzero and prints no
result.  The JAX compilation cache follows ``JAX_COMPILATION_CACHE_DIR``
when it is set, and ``.jax_cache/`` in the checkout otherwise.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"

TRAINERS = 4
PROMPT_LENS = "36,40,44,48"     # one prefill bucket (64), one table width
MAX_NEW = 16
REQUESTS = 4
KERNEL_TOL = 2e-2               # bf16 output rounding at |out| < 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit is counted in the compile seconds
    as its retrieval time)."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, snap) -> str:
        s, c, h = snap
        return (f"compile_s={self.seconds - s:.2f} "
                f"compiles={self.compiles - c} "
                f"cache_hits={self.cache_hits - h}")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def read_jsonl(path: Path):
    return [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]


# ---------------------------------------------------------------------------
# phase train: the paper's LTFB job
# ---------------------------------------------------------------------------


def ltfb_argv(tag: str, backend: str, seed: int, steps: int):
    run_dir = OUT / tag
    run_dir.mkdir(parents=True, exist_ok=True)
    for name in ("genealogy.jsonl", "trace.json"):
        (run_dir / name).unlink(missing_ok=True)
    return [
        "--arch", "icf-cyclegan", "--trainers", str(TRAINERS),
        "--backend", backend, "--batch", "128", "--rounds", "2",
        "--steps-per-round", str(steps), "--samples", "4096",
        "--samples-per-file", "256", "--seed", str(seed),
        "--data-dir", str(OUT / "jag"),
        "--genealogy", str(run_dir / "genealogy.jsonl"),
        "--trace-out", str(run_dir / "trace.json")]


def check_ltfb(np, orch, tag: str):
    """Finite losses and metrics, K decisions per round; returns the
    genealogy's match records."""
    run_dir = OUT / tag
    st = orch.stats()
    for i, d in enumerate(st["per_trainer"]):
        check(d["train_metrics"] and all(
            math.isfinite(v) for v in d["train_metrics"].values()),
            f"trainer {i} losses not finite: {d['train_metrics']}")
        check(d["tournament_metric"] is not None
              and math.isfinite(d["tournament_metric"]),
              f"trainer {i} tournament metric {d['tournament_metric']}")
    matches = [r for r in read_jsonl(run_dir / "genealogy.jsonl")
               if r["t"] == "match"]
    for rnd in range(2):
        ms = [m for m in matches if m["round"] == rnd]
        check(len(ms) == TRAINERS,
              f"round {rnd}: {len(ms)} adoption decisions, want {TRAINERS}")
        for m in ms:
            check(math.isfinite(m["m_local"]) and math.isfinite(m["m_other"]),
                  f"non-finite tournament metric {m}")
            check(m["adopted"] == (m["m_other"] < m["m_local"]),
                  f"decision disagrees with its metrics {m}")
    flops = st["flops_per_step"]
    check(flops is not None and flops > 0, f"FLOP probe gave {flops}")
    spans = json.loads((run_dir / "trace.json").read_text())["traceEvents"]
    steps = [e for e in spans if e.get("ph") == "X" and e["name"] == "step"]
    warm = sorted(e["dur"] / 1e3 for e in steps
                  if e["args"].get("step", 0) > len(steps) // TRAINERS // 2)
    log(f"{tag}: flops_per_step={flops:.4g} "
        f"round-1 step_ms median={np.median(warm):.3f} "
        f"max={max(warm):.3f} (n={len(warm)}, blocked on params) "
        f"round_wall_s={st['round_wall_seconds']:.2f} "
        f"tournament_s={st['tournament_seconds']:.2f}")
    for m in matches:
        log(f"{tag}: round={m['round']} trainer={m['trainer']} "
            f"partner={m['partner']} m_local={m['m_local']:.6f} "
            f"m_other={m['m_other']:.6f} adopted={m['adopted']}")
    return matches


def placement(jax, np, orch):
    """Per-trainer placement of the params: device kinds and ids."""
    out = []
    for t in orch.population.trainers:
        leaves = jax.tree.leaves(t.params)
        if all(isinstance(x, np.ndarray) for x in leaves):
            out.append("host numpy")
            continue
        devs = sorted({(d.platform, d.id) for x in leaves
                       for d in x.devices()})
        out.append(",".join(f"{p}:{i}" for p, i in devs))
    return out


def phase_train(jax, np, meter, seed: int):
    from repro.launch import ltfb

    snap = meter.snapshot()
    t0 = time.perf_counter()
    orch = ltfb.run(ltfb_argv("train", "host", seed, steps=4))
    wall = time.perf_counter() - t0
    check_ltfb(np, orch, "train")
    where = placement(jax, np, orch)
    log(f"train: placement per trainer: {where}")
    for i, w in enumerate(where):
        check(w.startswith("tpu:"), f"trainer {i} params on {w}")
    log(f"train: wall_s={wall:.2f} {meter.since(snap)} "
        f"peak_bytes_in_use={peak_bytes(jax)}")


# ---------------------------------------------------------------------------
# phase serve: qwen3-0.6b through the paged scheduler
# ---------------------------------------------------------------------------


def serve_once(seed: int, mesh=None):
    from repro.launch import serve

    argv = ["--arch", "qwen3-0.6b", "--requests", str(REQUESTS),
            "--max-new", str(MAX_NEW), "--prompt-lens", PROMPT_LENS,
            "--slots", "4", "--seed", str(seed)]
    if mesh:
        argv += ["--mesh", mesh]
    return serve.run_lm(serve.build_parser().parse_args(argv))


def tokens_of(out):
    return {rid: [int(t) for t in toks] for rid, toks in out["results"].items()}


def check_served(out, tag: str) -> None:
    res = out["results"]
    check(sorted(res) == list(range(REQUESTS)),
          f"{tag}: finished {sorted(res)}")
    for rid, toks in res.items():
        check(len(toks) == MAX_NEW,
              f"{tag}: request {rid} made {len(toks)} tokens")
    st = out["stats"]
    log(f"{tag}: requests={st['completed']} tokens_per_s="
        f"{st['tokens_per_s']:.2f} ttft_mean_s={st['ttft_mean_s']:.4f} "
        f"tpot_mean_s={st['tpot_mean_s']:.5f} "
        f"latency_mean_s={st['latency_mean_s']:.4f} "
        f"wall_s={st['wall_s']:.3f}")


def decode_hlo(jnp, sched) -> str:
    """Compiled HLO of the scheduler's plain decode step, at the shapes
    its decode rounds used."""
    from repro.serve import session as session_mod

    s, lay = sched.session, sched.session.layout
    B = lay.num_slots
    tables = lay.step_kwargs()["tables"]
    return session_mod._step_tables_fn.lower(
        s.params, s.cfg, jnp.zeros((B, 1), jnp.int32), lay.cache,
        jnp.full((B,), -1, jnp.int32), tables, None).compile().as_text()


def kernel_vs_oracle(jax, jnp, np, sched, seed: int) -> float:
    """The compiled Pallas kernel against the jnp oracle at the run's
    pool, batch and table shapes (decode K=1 and verify K=4), reading
    the second layer of a two-layer stacked pool."""
    from repro.kernels import paged_attention as pa
    from repro.kernels import ref

    cfg, lay = sched.cfg, sched.session.layout
    B, W, bs = lay.num_slots, lay.max_blocks_per_seq, lay.block_size
    P1 = lay.null_page + 1
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(seed)
    kernel = jax.jit(pa.paged_attention)
    worst = 0.0
    for K in (1, 4):
        q = rng.normal(size=(B, K, H, D) if K > 1 else (B, H, D))
        k = rng.normal(size=(2, P1, bs, Hkv, D))
        v = rng.normal(size=(2, P1, bs, Hkv, D))
        args = [jnp.asarray(a, jnp.bfloat16) for a in (q, k, v)]
        layer = jnp.int32(1)
        tables = jnp.asarray(rng.integers(0, P1 - 1, (B, W)), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, W * bs - K + 2, (B,)),
                              jnp.int32)
        got = kernel(*args, layer, tables, lengths).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attention_ref(
                *[a.astype(jnp.float32) for a in args], layer, tables,
                lengths)
        err = float(jnp.max(jnp.abs(got - want)))
        log(f"serve: paged kernel vs oracle K={K} B={B} H={H} Hkv={Hkv} "
            f"D={D} pages={P1} block={bs} W={W} max_abs_err={err:.3e}")
        worst = max(worst, err)
    return worst


def phase_serve(jax, jnp, np, meter, seed: int):
    snap = meter.snapshot()
    cold = serve_once(seed)
    check_served(cold, "serve cold")
    log(f"serve cold: {meter.since(snap)}")
    cold_results = tokens_of(cold)
    del cold
    gc.collect()
    snap = meter.snapshot()
    warm = serve_once(seed)
    check_served(warm, "serve warm")
    log(f"serve warm: {meter.since(snap)}")
    check(tokens_of(warm) == cold_results,
          "warm run's greedy tokens differ from cold run's")
    sched = warm["scheduler"]
    check("tpu_custom_call" in decode_hlo(jnp, sched),
          "decode HLO holds no Pallas kernel (tpu_custom_call)")
    log("serve: decode HLO holds the Pallas paged kernel (tpu_custom_call)")
    err = kernel_vs_oracle(jax, jnp, np, sched, seed)
    check(err < KERNEL_TOL, f"kernel vs oracle error {err} >= {KERNEL_TOL}")
    log(f"serve: peak_bytes_in_use={peak_bytes(jax)}")


# ---------------------------------------------------------------------------
# --chips 4: the paths that span chips, each against its one-chip twin
# ---------------------------------------------------------------------------


def compare_ltfb(jax, np, meter, seed: int):
    from repro.launch import ltfb

    got = {}
    for backend in ("host", "mesh"):
        snap = meter.snapshot()
        t0 = time.perf_counter()
        orch = ltfb.run(ltfb_argv(f"ltfb_{backend}", backend, seed, 2))
        log(f"ltfb {backend}: wall_s={time.perf_counter() - t0:.2f} "
            f"{meter.since(snap)}")
        got[backend] = check_ltfb(np, orch, f"ltfb_{backend}")
        log(f"ltfb {backend}: placement per trainer after the run: "
            f"{placement(jax, np, orch)}")
        del orch
        gc.collect()
    host = {(m["round"], m["trainer"]): m for m in got["host"]}
    mesh = {(m["round"], m["trainer"]): m for m in got["mesh"]}
    # round 0 trains identical populations on identical data, so each
    # trainer's own metric must agree; a decision is comparable only
    # where both backends paired the trainer with the same partner
    rel = max(abs(mesh[(0, i)]["m_local"] - host[(0, i)]["m_local"])
              / max(abs(host[(0, i)]["m_local"]), 1e-12)
              for i in range(TRAINERS))
    same = [k for k in host if host[k]["partner"] == mesh[k]["partner"]]
    agree = sum(host[k]["adopted"] == mesh[k]["adopted"] for k in same)
    log(f"ltfb mesh vs host: round-0 m_local max_rel_diff={rel:.3e}; "
        f"same-partner matches={len(same)}/{len(host)}, decisions "
        f"agree on {agree}/{len(same)}")
    check(rel < 1e-2, f"round-0 metrics differ across backends ({rel})")


def prefill_logits(jnp, np, sched, prompt):
    s = sched.session
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, _ = s._call_prefill(s.params, s.cfg, toks,
                                jnp.asarray([len(prompt) - 1], jnp.int32))
    return np.asarray(logits[0, -1].astype(jnp.float32))


def compare_serving(jax, jnp, np, meter, seed: int):
    got = {}
    for tag, mesh in (("one chip", None), ("2x2 mesh", "2,2")):
        snap = meter.snapshot()
        out = serve_once(seed, mesh)
        check_served(out, f"serve {tag}")
        log(f"serve {tag}: {meter.since(snap)}")
        prompt = np.arange(1, 41) * 997 % out["scheduler"].cfg.vocab_size
        logits = prefill_logits(jnp, np, out["scheduler"], prompt)
        check(np.isfinite(logits).all(), f"{tag}: non-finite logits")
        got[tag] = (tokens_of(out), logits)
        del out
        gc.collect()
    (r1, l1), (r4, l4) = got["one chip"], got["2x2 mesh"]
    same = sum(a == b for r in r1 for a, b in zip(r1[r], r4[r]))
    first = next((i for i in range(MAX_NEW)
                  if any(r1[r][i] != r4[r][i] for r in r1)), None)
    log(f"serve 2x2 mesh vs one chip: tokens agree {same}/"
        f"{REQUESTS * MAX_NEW} (first divergence at step {first}); "
        f"prefill max_abs_logit_diff={float(np.max(np.abs(l1 - l4))):.4e} "
        f"(logit scale {float(np.max(np.abs(l1))):.3f}); "
        f"argmax agree={int(np.argmax(l1)) == int(np.argmax(l4))}")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository "
              f"(no src/repro beside {Path(__file__).name})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    OUT.mkdir(exist_ok=True)
    meter = CompileMeter(jax.monitoring)
    log(f"device_kind={devs[0].device_kind} platform={devs[0].platform} "
        f"count={len(devs)} jax={jax.__version__} cache_dir={cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        compare_ltfb(jax, np, meter, args.seed)
        compare_serving(jax, jnp, np, meter, args.seed)
    else:
        phase_train(jax, np, meter, args.seed)
        gc.collect()
        phase_serve(jax, jnp, np, meter, args.seed)
    log(f"total_s={time.perf_counter() - t0:.2f} compile_s="
        f"{meter.seconds:.2f} compiles={meter.compiles} "
        f"cache_hits={meter.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
