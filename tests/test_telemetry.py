"""Telemetry tests: bounded latency series, per-request span lifecycle
completeness (every submitted rid ends in exactly one terminal event,
shed/cancelled included), Prometheus exposition wellformedness +
histogram/counter agreement, Chrome-trace export schema, loopback
mesh-stats aggregation, the gateway observability endpoints
(readyz gate, content negotiation, /debug/trace, /debug/profile), and
the --no-telemetry path."""
import asyncio
import dataclasses
import json
import re
import threading

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve import telemetry as telemetry_mod
from repro.serve.gateway import Gateway
from repro.serve.metrics import (BoundedSeries, Histogram, LATENCY_BUCKETS,
                                 ServeStats, percentile)
from repro.serve.scheduler import Request, Scheduler

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                              dtype="float32")
    params, _ = init_lm(cfg, KEY)
    return cfg, params


def _prompt(cfg, n=8, seed=3):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


@pytest.fixture(scope="module")
def mixed_run(served):
    """One mixed-outcome trace: completions + a deadline shed + a
    cancel, served to completion; returns the scheduler."""
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    sched.submit(Request(rid="a", prompt=_prompt(cfg), max_new=4))
    sched.step()                       # occupy the only slot
    sched.submit(Request(rid="late", prompt=_prompt(cfg), max_new=4,
                         ttft_deadline_ms=1e-3))
    sched.submit(Request(rid="ok", prompt=_prompt(cfg), max_new=4))
    sched.submit(Request(rid="victim", prompt=_prompt(cfg), max_new=4))
    assert sched.shed_expired() == ["late"]
    assert sched.cancel("victim")
    results = sched.run()
    assert set(results) == {"a", "ok"}
    return sched


# -- bounded latency series -------------------------------------------------


def test_bounded_series_exact_then_reservoir():
    bs = BoundedSeries(exact_cap=16, reservoir=8)
    vals = [float(i) for i in range(10)]
    for v in vals:
        bs.append(v)
    # short runs answer from the exact list
    assert bs.exact and list(bs) == vals
    assert bs.count == 10 and len(bs) == 10
    assert bs.percentile(50) == percentile(vals, 50)
    assert bs.percentile(95) == percentile(vals, 95)
    # beyond the cap: bounded reservoir + histogram, totals stay exact
    for v in range(10, 200):
        bs.append(float(v))
    assert not bs.exact
    assert len(bs._sample) == 8        # bounded memory
    assert len(bs) == 200 and bs.count == 200
    assert bs.hist.total == 200
    assert bs.sum == pytest.approx(sum(range(200)))
    assert bs.mean == pytest.approx(sum(range(200)) / 200)
    p = bs.percentile(50)
    assert 0.0 <= p <= 199.0           # answered from the reservoir
    assert percentile(bs, 50) == p     # percentile() accepts the series


def test_histogram_bucket_counts():
    h = Histogram(LATENCY_BUCKETS)
    for v in (0.0005, 0.002, 0.002, 0.7, 1e9):
        h.observe(v)
    assert h.total == 5
    assert h.sum == pytest.approx(0.0005 + 0.002 + 0.002 + 0.7 + 1e9)
    by_le = dict(h.bucket_counts())
    assert by_le[0.001] == 1           # 0.0005
    assert by_le[0.0025] == 2          # the two 2ms observations
    assert by_le[1.0] == 1             # 0.7
    # the overflow observation lands only in +Inf (counts[-1])
    assert sum(n for _, n in h.bucket_counts()) == 4
    assert h.counts[-1] == 1


# -- span lifecycle ---------------------------------------------------------


def test_every_request_ends_in_exactly_one_terminal(mixed_run):
    sched = mixed_run
    evs = sched.telemetry.tracer.export()["traceEvents"]
    term = {}
    names = {}
    for ev in evs:
        rid = ev.get("args", {}).get("rid")
        if rid is None:
            continue
        names.setdefault(rid, set()).add(ev["name"])
        if ev.get("args", {}).get("terminal"):
            term.setdefault(rid, []).append(ev["name"])
    for rid in ("a", "late", "ok", "victim"):
        assert len(term.get(rid, [])) == 1, \
            f"{rid}: terminals {term.get(rid)}"
    assert term["a"] == ["finish"] and term["ok"] == ["finish"]
    assert term["late"] == ["shed"]
    assert term["victim"] == ["cancel"]
    # completed requests carry the full chain
    for rid in ("a", "ok"):
        assert {"enqueue", "admit", "first_token", "finish"} <= names[rid]


def test_chrome_trace_export_schema(mixed_run):
    out = mixed_run.telemetry.tracer.export()
    # loads/dumps round-trip: the gateway serves exactly this object
    out = json.loads(json.dumps(out))
    assert isinstance(out["traceEvents"], list) and out["traceEvents"]
    assert out["otherData"]["dropped"] == 0
    for ev in out["traceEvents"]:
        assert ev["ph"] in ("M", "X", "i")
        assert isinstance(ev["name"], str) and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # per-request rows are named via thread_name metadata
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert any(e["args"]["name"] == "scheduler" for e in meta)
    assert any(e["args"]["name"] == "req a" for e in meta)


def test_telemetry_disabled_emits_no_events(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=2, max_len=32,
                      telemetry=False)
    for rid in ("x", "y"):
        sched.submit(Request(rid=rid, prompt=_prompt(cfg), max_new=4))
    results = sched.run()
    assert set(results) == {"x", "y"}
    assert len(sched.telemetry.tracer.events) == 0
    # phase wall-time attribution still accumulates (it feeds /metrics)
    tel = sched.telemetry
    assert tel.phase_seconds.get("decode", 0.0) > 0.0
    # through the span API: a phase span adds its time, a span that is
    # not a phase adds none, and neither reaches the disabled ring
    before, calls = tel.phase_seconds["decode"], tel.phase_calls["decode"]
    with tel.span("decode") as sp:
        with tel.span("sample"):
            pass
    assert tel.phase_seconds["decode"] == pytest.approx(
        before + sp.seconds)
    assert tel.phase_calls["decode"] == calls + 1
    assert "sample" not in tel.phase_seconds
    assert len(tel.tracer.events) == 0


# -- prometheus exposition --------------------------------------------------

_SAMPLE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
                     r'(\{[^}]*\})? (NaN|[+-]?[0-9eE.+-]+|[+-]Inf)$')


def _parse_prom(text):
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE "))
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed sample line: {line!r}"
        samples[m.group(1) + (m.group(2) or "")] = m.group(3)
    return samples


def test_prometheus_exposition(mixed_run):
    sched = mixed_run
    text = telemetry_mod.scheduler_prometheus(sched)
    samples = _parse_prom(text)
    s = sched.stats
    assert samples["repro_serve_completed_total"] == str(s.completed)
    assert samples["repro_serve_shed_deadline_total"] == "1"
    assert samples["repro_serve_cancelled_total"] == "1"
    assert samples["repro_serve_decode_tokens_total"] == \
        str(s.decode_tokens)
    # histogram: one ttft observation per completion, buckets cumulative
    assert samples["repro_serve_ttft_seconds_count"] == str(s.completed)
    infb = 'repro_serve_ttft_seconds_bucket{le="+Inf"}'
    assert samples[infb] == str(s.completed)
    cum = [int(v) for k, v in samples.items()
           if k.startswith("repro_serve_ttft_seconds_bucket")]
    assert cum == sorted(cum), "buckets must be cumulative"
    # per-shard pool occupancy + phase attribution ride along: every
    # phase span the scheduler opened, at the seconds it accumulated
    assert 'repro_serve_pool_high_water_blocks{shard="0"}' in samples
    phases = sched.telemetry.phase_seconds
    assert {"admit", "prefill", "decode"} <= set(phases)
    for ph, secs in phases.items():
        key = f'repro_serve_phase_seconds_total{{phase="{ph}"}}'
        assert float(samples[key]) == pytest.approx(secs)


def test_prometheus_text_handles_empty_stats():
    text = telemetry_mod.prometheus_text(ServeStats(slots=2))
    samples = _parse_prom(text)
    assert samples["repro_serve_submitted_total"] == "0"
    assert samples["repro_serve_latency_seconds_count"] == "0"


# -- mesh aggregation (loopback channel, world size 1) ----------------------


def test_mesh_loopback_stats_aggregation(served):
    from repro.serve.mesh import MeshScheduler
    cfg, params = served
    sched = MeshScheduler(cfg, params, num_slots=2, max_len=32)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=_prompt(cfg, seed=i),
                             max_new=4))
    results = sched.run(max_steps=200)
    assert len(results) == 3
    # the loopback gather ran every step: host-0's latest snapshot of
    # itself must equal its own live counters
    assert 0 in sched.remote_stats
    snap = sched.remote_stats[0]
    assert snap["completed"] == sched.stats.completed == 3
    assert snap["decode_steps"] == sched.stats.decode_steps
    assert snap["shards"], "per-data-shard pool snapshots must ride along"
    assert snap["shards"][0]["high_water_blocks"] > 0
    # and the exposition emits them as per-rank mesh series
    samples = _parse_prom(telemetry_mod.scheduler_prometheus(sched))
    assert samples['repro_serve_mesh_completed_total{rank="0"}'] == "3"
    assert 'repro_serve_mesh_pool_high_water_blocks' \
           '{rank="0",shard="0"}' in samples


# -- gateway observability endpoints ----------------------------------------


async def _http(port, method, path, body=None, headers=None):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    w.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n{extra}"
             f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload)
    await w.drain()
    data = await r.read()
    w.close()
    return data.decode()


def _status(resp):
    return int(resp.split()[1])


def _body(resp):
    return resp.split("\r\n\r\n", 1)[1]


def _run(coro, timeout=300):
    return asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(coro, timeout))


def test_gateway_readyz_gates_on_warmup(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    gate = threading.Event()
    gw = Gateway(sched, warmup=gate.wait)

    async def go():
        await gw.start()
        cold = await _http(gw.port, "GET", "/readyz")
        live = await _http(gw.port, "GET", "/healthz")
        gate.set()                     # weight load / compile finished
        while _status(await _http(gw.port, "GET", "/readyz")) != 200:
            await asyncio.sleep(0.01)
        warm = await _http(gw.port, "GET", "/readyz")
        await gw.stop()
        return cold, live, warm

    cold, live, warm = _run(go())
    assert _status(cold) == 503 and not json.loads(_body(cold))["ready"]
    # liveness stays 200 through cold start — only readiness gates
    assert _status(live) == 200 and not json.loads(_body(live))["ready"]
    wd = json.loads(_body(warm))
    assert wd["ready"] and "queued" in wd and "slots_busy" in wd


def test_gateway_metrics_trace_and_profile(served, tmp_path):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    gw = Gateway(sched)
    prof_dir = str(tmp_path / "prof")

    async def go():
        await gw.start()
        armed = await _http(gw.port, "POST", "/debug/profile",
                            {"steps": 2, "dir": prof_dir})
        bad = await _http(gw.port, "POST", "/debug/profile",
                          {"steps": 0})
        gen = await _http(gw.port, "POST", "/v1/generate",
                          {"prompt": _prompt(cfg).tolist(), "max_new": 4,
                           "rid": "r", "stream": False})
        prom = await _http(gw.port, "GET", "/metrics")
        js = await _http(gw.port, "GET", "/metrics",
                         headers={"Accept": "application/json"})
        trace = await _http(gw.port, "GET", "/debug/trace")
        await gw.stop()
        return armed, bad, gen, prom, js, trace

    armed, bad, gen, prom, js, trace = _run(go())
    assert _status(armed) == 200 and json.loads(_body(armed))["armed"]
    assert _status(bad) == 400
    assert _status(gen) == 200
    # default scrape is Prometheus text with the versioned content type
    assert "text/plain; version=0.0.4" in prom
    samples = _parse_prom(_body(prom))
    assert samples["repro_serve_completed_total"] == "1"
    # JSON summary preserved behind content negotiation
    jd = json.loads(_body(js))
    assert jd["completed"] == 1 and "phase_seconds" in jd
    # trace export: full chain for the gateway-served request
    td = json.loads(_body(trace))
    names = {e["name"] for e in td["traceEvents"]
             if e.get("args", {}).get("rid") == "r"}
    assert {"enqueue", "first_token", "finish"} <= names
    # the armed window wrapped real steps and closed
    assert sched.telemetry.profiles_taken == 1
    assert (tmp_path / "prof").is_dir()


# -- structured JSON logs ---------------------------------------------------


def test_json_log_events(capsys):
    telemetry_mod.enable_json_logs()
    try:
        telemetry_mod.log_event("unit", n=1, bad=float("nan"),
                                nested={"t": (1, 2)})
        st = ServeStats(slots=1)
        st.start()
        st.stop()
        st.report()
    finally:
        telemetry_mod.enable_json_logs(False)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    recs = [json.loads(ln) for ln in lines]   # every line is valid JSON
    unit = next(r for r in recs if r["event"] == "unit")
    assert unit["n"] == 1 and unit["bad"] is None
    assert unit["nested"] == {"t": [1, 2]}
    assert unit["ts_monotonic"] > 0
    report = next(r for r in recs if r["event"] == "serve_report")
    assert report["slots"] == 1 and "tokens_per_s" in report
    # disabled again: no further records
    telemetry_mod.log_event("after")
    assert "after" not in capsys.readouterr().out


# -- robustness counters ----------------------------------------------------


def test_prometheus_robustness_counters_present():
    """The fault-tolerance counters ride the standard exposition: typed,
    helped, zero-valued on an idle server (so dashboards can alert on
    any increase without first causing a fault)."""
    s = ServeStats(slots=2)
    text = telemetry_mod.prometheus_text(s)
    samples = _parse_prom(text)
    for name in ("fault_injected", "swap_rejected_corrupt",
                 "plan_retries", "journal_replayed"):
        key = f"repro_serve_{name}_total"
        assert samples[key] == "0", key
        assert f"# TYPE {key} counter" in text
    s.fault_injected = 3
    s.swap_rejected_corrupt = 1
    s.plan_retries = 2
    s.journal_replayed = 4
    samples = _parse_prom(telemetry_mod.prometheus_text(s))
    assert samples["repro_serve_fault_injected_total"] == "3"
    assert samples["repro_serve_swap_rejected_corrupt_total"] == "1"
    assert samples["repro_serve_plan_retries_total"] == "2"
    assert samples["repro_serve_journal_replayed_total"] == "4"
    d = s.as_dict()
    assert d["fault_injected"] == 3 and d["journal_replayed"] == 4
