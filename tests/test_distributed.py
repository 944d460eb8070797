"""Multi-process mesh serving tests (real OS processes over
``jax.distributed``): 2-process vs 1-process token identity through
the spawn CLI, follower-replica result identity, and the
coordination-service channel's dead-peer timeout (a clean error
instead of a hang)."""
import json
import os
import socket
import subprocess
import sys

import pytest

from repro.launch.distributed import build_parser, find_free_port, main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _can_bind() -> bool:
    """The coordinator needs a bindable local TCP port."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.bind(("127.0.0.1", 0))
        return True
    except OSError:
        return False


needs_loopback = pytest.mark.skipif(
    not _can_bind(), reason="cannot bind a local TCP port "
                            "(no loopback for the jax coordinator)")


def _run_cli(args, out_json, timeout=560):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.path.join(ROOT, "src"),
                "PYTHONUNBUFFERED": "1"})
    cmd = [sys.executable, "-m", "repro.launch.distributed",
           "--smoke", "--requests", "3", "--max-new", "6",
           "--prompt-lens", "8,12", "--out-json", out_json, *args]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    return r


def test_build_parser_smoke():
    args = build_parser().parse_args(
        ["--procs", "2", "--step-timeout", "5"])
    assert args.procs == 2 and args.step_timeout == 5.0
    assert find_free_port() > 0


def test_spawn_refuses_without_explicit_cpu(monkeypatch):
    """Spawned workers would each open every chip of the host, so the
    spawner starts nothing unless the CPU was asked for explicitly."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="JAX_PLATFORMS=cpu"):
        main(["--procs", "2", "--smoke"])


@needs_loopback
def test_two_process_token_identity(tmp_path):
    """The tentpole acceptance: a 2-process run (host-0 scheduler +
    follower replica, plans over the coordination service) produces
    token-identical results to the single-process run, on BOTH
    processes."""
    one = str(tmp_path / "one.json")
    two = str(tmp_path / "two.json")
    _run_cli(["--procs", "1"], one)
    r = _run_cli(["--procs", "2", "--step-timeout", "120"], two)
    assert "CoordServiceChannel" in r.stdout
    a = json.load(open(one))
    b = json.load(open(two))
    follower = json.load(open(two + ".p1"))
    assert a["results"] == b["results"] == follower["results"]
    assert len(a["results"]) == 3
    assert all(len(t) == 6 for t in a["results"].values())
    # both processes saw the same scheduler trajectory
    assert b["stats"]["decode_steps"] == follower["stats"]["decode_steps"]
    assert b["stats"]["prefills"] == follower["stats"]["prefills"]
    # mesh-wide stats aggregation: host-0's export covers every rank,
    # and the gathered counters equal each process's own stats
    ms = b["mesh_stats"]
    assert sorted(ms) == ["0", "1"]
    for rank, own in (("0", b), ("1", follower)):
        for k in ("completed", "decode_steps", "prefills",
                  "decode_tokens"):
            assert ms[rank][k] == own["stats"][k], (rank, k)
        assert ms[rank]["shards"][0]["high_water_blocks"] > 0
    # the Prometheus sidecar host-0 writes covers both ranks
    prom = open(two + ".prom").read()
    assert prom.startswith("# HELP repro_serve_")
    assert f'repro_serve_mesh_completed_total{{rank="1"}} ' \
           f'{follower["stats"]["completed"]}' in prom


@needs_loopback
def test_replicated_feed_dedupes(tmp_path):
    """``--feed replicated``: followers also submit the trace locally;
    the plan's submits must be recognized as already-local copies (no
    duplicate enqueue), with identical results."""
    two = str(tmp_path / "rep.json")
    _run_cli(["--procs", "2", "--feed", "replicated",
              "--step-timeout", "120"], two)
    host0 = json.load(open(two))
    follower = json.load(open(two + ".p1"))
    assert host0["results"] == follower["results"]
    assert follower["stats"]["completed"] == 3


@needs_loopback
def test_dead_peer_times_out_not_hangs():
    """A follower that dies mid-serve must surface as a broadcast
    timeout error on the survivor, not an indefinite hang."""
    port = find_free_port()
    script = r"""
import os
import sys
import jax
pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="127.0.0.1:%d",
                           num_processes=2, process_id=pid,
                           initialization_timeout=60)
from repro.serve.mesh import CoordServiceChannel, StepPlan
ch = CoordServiceChannel(timeout_s=3.0, namespace="t/dead")
if pid == 1:
    os._exit(0)          # hard death before joining the step barrier
try:
    ch.broadcast(StepPlan())
except RuntimeError as e:
    assert "timed out" in str(e), e
    print("TIMEOUT-OK", flush=True)
    os._exit(0)          # skip the atexit shutdown handshake: the
                         # peer it would wait for is already gone
print("UNEXPECTED: broadcast returned", flush=True)
os._exit(1)
""" % port
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.path.join(ROOT, "src")})
    procs = [subprocess.Popen([sys.executable, "-c", script, str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(2)]
    out0, err0 = procs[0].communicate(timeout=120)
    procs[1].communicate(timeout=120)
    assert procs[0].returncode == 0, f"{out0}\n{err0}"
    assert "TIMEOUT-OK" in out0
