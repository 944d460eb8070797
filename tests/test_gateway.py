"""Gateway tests: streaming order/parity, SLO-aware admission
(max-queue overload -> 429, TTFT-deadline shedding), bounded-buffer
backpressure, client-disconnect cancellation, and the scheduler-level
max_queue / cancel regressions the gateway relies on."""
import asyncio
import dataclasses
import json
import threading

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.lm import init_lm
from repro.serve.gateway import Gateway, _Stream
from repro.serve.scheduler import Overloaded, Request, Scheduler

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                              dtype="float32")
    params, _ = init_lm(cfg, KEY)
    return cfg, params


def _prompt(cfg, n=8, seed=3):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).tolist()


# -- raw HTTP client helpers (stdlib only, like the gateway itself) ---------


async def _http(port, method, path, body=None, read_all=True,
                headers=None):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    w.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n{extra}"
             f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload)
    await w.drain()
    data = await r.read() if read_all else b""
    w.close()
    return data.decode()


def _status(resp: str) -> int:
    return int(resp.split()[1])


def _ndjson(resp: str):
    """Decode a chunked NDJSON body into its records."""
    body = resp.split("\r\n\r\n", 1)[1]
    recs = []
    while body:
        size, _, rest = body.partition("\r\n")
        n = int(size, 16)
        if n == 0:
            break
        recs.append(json.loads(rest[:n]))
        body = rest[n + 2:]
    return recs


def _run(coro, timeout=300):
    return asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(coro, timeout))


def _hold_after_first_token(sched, rid) -> threading.Event:
    """Gate the scheduler's step: once ``rid`` has a token, ``step()``
    does nothing until the returned event is set, so the request stays
    in flight however fast the machine decodes.  The gateway's driver
    keeps taking in, shedding and answering other requests meanwhile."""
    release = threading.Event()
    inner = sched.step

    def step():
        act = sched.active.get(rid)
        if act is not None and act.tokens and not release.is_set():
            release.wait(0.01)
            return
        inner()

    sched.step = step
    return release


# -- scheduler-level admission regressions ----------------------------------


def test_scheduler_max_queue_overload(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=2, max_len=32, max_queue=2)
    p = np.asarray(_prompt(cfg), np.int32)
    sched.submit(Request(rid="a", prompt=p, max_new=4))
    sched.submit(Request(rid="b", prompt=p, max_new=4))
    with pytest.raises(Overloaded):
        sched.submit(Request(rid="c", prompt=p, max_new=4))
    assert sched.stats.shed_overload == 1
    # shedding is not rejection: the request was well-formed
    assert sched.stats.rejected == 0
    # draining the queue reopens admission
    sched.run()
    sched.submit(Request(rid="c", prompt=p, max_new=4))
    assert len(sched.queue) == 1


def test_scheduler_cancel_releases_resources(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=2, max_len=32)
    p = np.asarray(_prompt(cfg), np.int32)
    for rid in ("a", "b", "c"):
        sched.submit(Request(rid=rid, prompt=p, max_new=8))
    sched.step()                      # a (and maybe b) admitted
    free0 = sched.pool.blocks.free_blocks
    assert sched.cancel("a")          # in-flight
    assert sched.cancel("c")          # still queued
    assert not sched.cancel("zz")     # unknown
    assert sched.pool.blocks.free_blocks > free0
    assert sched.stats.cancelled == 2
    results = sched.run()             # b must still complete
    assert set(results) == {"b"}
    assert "a" not in sched.results and "c" not in sched.results


def test_scheduler_shed_expired_deadline(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    p = np.asarray(_prompt(cfg), np.int32)
    sched.submit(Request(rid="a", prompt=p, max_new=4))
    sched.step()                      # occupy the only slot
    sched.submit(Request(rid="late", prompt=p, max_new=4,
                         ttft_deadline_ms=1e-3))
    sched.submit(Request(rid="ok", prompt=p, max_new=4))
    shed = sched.shed_expired()
    assert shed == ["late"]
    assert sched.stats.shed_deadline == 1
    results = sched.run()
    assert set(results) == {"a", "ok"}


# -- gateway integration ----------------------------------------------------


def test_gateway_stream_matches_nonstream_and_orders_tokens(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=2, max_len=32)
    gw = Gateway(sched)

    async def go():
        await gw.start()
        body = {"prompt": _prompt(cfg), "max_new": 6}
        streamed = await _http(gw.port, "POST", "/v1/generate",
                               {**body, "rid": "s"})
        plain = await _http(gw.port, "POST", "/v1/generate",
                            {**body, "rid": "p", "stream": False})
        health = await _http(gw.port, "GET", "/healthz")
        metrics = await _http(gw.port, "GET", "/metrics",
                              headers={"Accept": "application/json"})
        missing = await _http(gw.port, "GET", "/nope")
        await gw.stop()
        return streamed, plain, health, metrics, missing

    streamed, plain, health, metrics, missing = _run(go())
    recs = _ndjson(streamed)
    toks = [r["token"] for r in recs if "token" in r]
    assert recs[-1] == {"rid": "s", "done": True, "ntok": 6}
    assert _status(plain) == 200
    assert json.loads(plain.split("\r\n\r\n", 1)[1])["tokens"] == toks
    assert len(toks) == 6
    assert _status(health) == 200 and _status(missing) == 404
    md = json.loads(metrics.split("\r\n\r\n", 1)[1])
    assert md["completed"] == 2 and md["submitted"] == 2


def test_gateway_sheds_overload_with_429(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=72,
                      max_queue=1)
    gw = Gateway(sched)

    async def go():
        await gw.start()
        # occupy the single slot with a long request, confirmed by its
        # first streamed token (so admission has definitely happened)
        r, w = await asyncio.open_connection("127.0.0.1", gw.port)
        body = json.dumps({"prompt": _prompt(cfg), "max_new": 64,
                           "rid": "hog"}).encode()
        w.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await w.drain()
        await r.readuntil(b"token")
        # now burst: one fits the queue, the rest must shed with 429
        burst = await asyncio.gather(*[
            _http(gw.port, "POST", "/v1/generate",
                  {"prompt": _prompt(cfg), "max_new": 4,
                   "rid": f"b{i}"}) for i in range(3)])
        w.close()
        await gw.stop()
        return burst

    burst = _run(go())
    codes = sorted(_status(b) for b in burst)
    assert codes.count(429) >= 1, codes
    shed = [b for b in burst if _status(b) == 429]
    assert all("Retry-After" in b for b in shed)
    assert sched.stats.shed_overload >= 1


def test_gateway_deadline_shed_is_429(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=24)
    release = _hold_after_first_token(sched, "hog")
    gw = Gateway(sched)

    async def go():
        await gw.start()
        r, w = await asyncio.open_connection("127.0.0.1", gw.port)
        body = json.dumps({"prompt": _prompt(cfg), "max_new": 8,
                           "rid": "hog"}).encode()
        w.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await w.drain()
        await r.readuntil(b"token")    # slot occupied, held there
        late = await _http(gw.port, "POST", "/v1/generate",
                           {"prompt": _prompt(cfg), "max_new": 4,
                            "rid": "late", "ttft_deadline_ms": 0.001})
        release.set()
        hog = await r.read()           # the hog streams to its end
        w.close()
        await gw.stop()
        return late, hog

    late, hog = _run(go())
    assert _status(late) == 429
    assert "deadline" in late
    assert sched.stats.shed_deadline == 1
    assert len(sched.results["hog"]) == 8 and b"error" not in hog


def test_gateway_bad_request_is_400(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    gw = Gateway(sched)

    async def go():
        await gw.start()
        missing = await _http(gw.port, "POST", "/v1/generate", {})
        toolong = await _http(gw.port, "POST", "/v1/generate",
                              {"prompt": _prompt(cfg, 8),
                               "max_new": 4096})
        await gw.stop()
        return missing, toolong

    missing, toolong = _run(go())
    assert _status(missing) == 400
    assert _status(toolong) == 400
    assert sched.stats.rejected == 1


def test_backpressure_cancels_slow_consumer(served):
    # driver-side publication unit: a consumer that stops draining its
    # bounded stream queue gets the request cancelled, not an
    # unbounded buffer
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=32)
    gw = Gateway(sched, stream_buffer=2)
    loop = asyncio.new_event_loop()
    gw.loop = loop
    st = _Stream(rid="slow", q=asyncio.Queue())
    gw._streams["slow"] = st
    for i in range(5):                # consumer never drains
        gw._post(st, ("tok", i))
    loop.run_until_complete(asyncio.sleep(0))
    assert st.error is not None and "backpressure" in st.error
    assert list(gw._cancels) == ["slow"]
    assert st.q.qsize() == 2          # bounded: only the buffer landed
    assert "slow" not in gw._streams
    # further publications are dropped, not queued
    gw._post(st, ("tok", 99))
    assert st.q.qsize() == 2
    loop.close()


def test_gateway_client_disconnect_frees_slot(served):
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=136)
    gw = Gateway(sched)

    async def go():
        await gw.start()
        r, w = await asyncio.open_connection("127.0.0.1", gw.port)
        body = json.dumps({"prompt": _prompt(cfg), "max_new": 128,
                           "rid": "gone"}).encode()
        w.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await w.drain()
        await r.readuntil(b"token")
        # hard-close mid-stream: the server must cancel the request
        w.transport.abort()
        # the freed slot must serve a new request to completion
        nxt = await _http(gw.port, "POST", "/v1/generate",
                          {"prompt": _prompt(cfg, seed=5), "max_new": 4,
                           "rid": "after", "stream": False})
        await gw.stop()
        return nxt

    nxt = _run(go())
    assert _status(nxt) == 200
    assert len(json.loads(nxt.split("\r\n\r\n", 1)[1])["tokens"]) == 4
    assert sched.stats.cancelled == 1
    assert "gone" not in sched.results


# -- fault tolerance: drain + idempotent retries ----------------------------


def _header(resp: str, name: str):
    for line in resp.split("\r\n\r\n", 1)[0].split("\r\n")[1:]:
        k, _, v = line.partition(":")
        if k.strip().lower() == name.lower():
            return v.strip()
    return None


def test_gateway_drain_refuses_new_work_with_retry_after(served):
    """begin_drain(): /readyz flips to 503 and new generates get 503 +
    Retry-After, while an in-flight request keeps streaming to done."""
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=40)
    gw = Gateway(sched)

    async def go():
        await gw.start()
        ready_before = await _http(gw.port, "GET", "/readyz")
        r, w = await asyncio.open_connection("127.0.0.1", gw.port)
        body = json.dumps({"prompt": _prompt(cfg), "max_new": 16,
                           "rid": "inflight"}).encode()
        w.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await w.drain()
        await r.readuntil(b"token")            # streaming has begun
        gw.begin_drain()
        ready_after = await _http(gw.port, "GET", "/readyz")
        refused = await _http(gw.port, "POST", "/v1/generate",
                              {"prompt": _prompt(cfg), "max_new": 2,
                               "rid": "late", "stream": False})
        rest = (await r.read()).decode()       # in-flight finishes
        w.close()
        while not gw.drained():
            await asyncio.sleep(0.01)
        await gw.stop()
        return ready_before, ready_after, refused, rest

    ready_before, ready_after, refused, rest = _run(go())
    assert _status(ready_before) == 200
    assert _status(ready_after) == 503
    assert _header(ready_after, "Retry-After") is not None
    assert _status(refused) == 503
    assert _header(refused, "Retry-After") is not None
    assert "draining" in refused
    assert '"done": true' in rest.lower()
    assert "late" not in sched.results and "inflight" in sched.results


def test_gateway_idempotency_key_dedups_retries(served):
    """The same Idempotency-Key never double-admits: 409 while the
    original is in flight, a 200 replay once it finished."""
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=40)
    gw = Gateway(sched)

    async def go():
        await gw.start()
        first = await _http(gw.port, "POST", "/v1/generate",
                            {"prompt": _prompt(cfg), "max_new": 4,
                             "rid": "orig", "stream": False},
                            headers={"Idempotency-Key": "abc"})
        replay = await _http(gw.port, "POST", "/v1/generate",
                             {"prompt": _prompt(cfg), "max_new": 4},
                             headers={"Idempotency-Key": "abc"})
        fresh = await _http(gw.port, "POST", "/v1/generate",
                            {"prompt": _prompt(cfg), "max_new": 4,
                             "rid": "other", "stream": False},
                            headers={"Idempotency-Key": "xyz"})
        await gw.stop()
        return first, replay, fresh

    first, replay, fresh = _run(go())
    assert _status(first) == 200 and _status(fresh) == 200
    body1 = json.loads(first.split("\r\n\r\n", 1)[1])
    body2 = json.loads(replay.split("\r\n\r\n", 1)[1])
    assert _status(replay) == 200 and body2["idempotent_replay"]
    assert body2["tokens"] == body1["tokens"]
    assert body2["rid"] == "orig"
    assert sched.stats.submitted == 2          # replay never admitted


def test_gateway_idempotency_conflict_while_in_flight(served):
    """A retry racing the original gets 409 + Retry-After instead of a
    duplicate stream; seeding from a journal map works the same way."""
    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=24)
    release = _hold_after_first_token(sched, "slow")
    gw = Gateway(sched)

    async def go():
        await gw.start()
        r, w = await asyncio.open_connection("127.0.0.1", gw.port)
        body = json.dumps({"prompt": _prompt(cfg), "max_new": 8,
                           "rid": "slow"}).encode()
        w.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                 f"Idempotency-Key: race\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        await w.drain()
        await r.readuntil(b"token")            # admitted, held in flight
        dup = await _http(gw.port, "POST", "/v1/generate",
                          {"prompt": _prompt(cfg), "max_new": 4},
                          headers={"Idempotency-Key": "race"})
        release.set()
        await r.read()                         # the original completes
        w.close()
        await gw.stop()
        return dup

    dup = _run(go())
    assert _status(dup) == 409
    assert _header(dup, "Retry-After") is not None
    assert json.loads(dup.split("\r\n\r\n", 1)[1])["rid"] == "slow"


def test_gateway_seed_idempotency_replays_journaled_result(served):
    """Across a restart: a finished rid preloaded from the journal
    (results + idempotency map) satisfies a client retry without
    re-decoding."""
    from repro.serve import journal as journal_mod

    cfg, params = served
    sched = Scheduler(cfg, params, num_slots=1, max_len=40)
    sched.results["done-rid"] = np.asarray([3, 1, 4], np.int32)
    gw = Gateway(sched)
    gw.seed_idempotency({"restart-key": ("done-rid", True)})

    async def go():
        await gw.start()
        resp = await _http(gw.port, "POST", "/v1/generate",
                           {"prompt": _prompt(cfg), "max_new": 4},
                           headers={"Idempotency-Key": "restart-key"})
        await gw.stop()
        return resp

    resp = _run(go())
    assert _status(resp) == 200
    body = json.loads(resp.split("\r\n\r\n", 1)[1])
    assert body["tokens"] == [3, 1, 4] and body["idempotent_replay"]
    assert sched.stats.submitted == 0          # nothing re-decoded
