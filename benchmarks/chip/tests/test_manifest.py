"""BENCHMARK.json against the contract, and the files it names."""
import json
import re
import subprocess
import sys

import pytest

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_manifest()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_and_units(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        names.append(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for n in names:
        assert NAME.match(n), n
    assert len(set(names)) == len(names)


def test_end_to_end(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_what_it_needs(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e_of = {c: {m["name"] for m in bench["end_to_end"]
                  if c in m.get("workloads", cells)} for c in cells}
    for c in cells:
        assert "setup_s" in e2e_of[c] and len(e2e_of[c]) >= 2
        assert any(c in m.get("workloads", cells)
                   for m in bench["per_layer"])
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert len(m["layer"]) <= 200
        for c in m.get("workloads", cells):
            assert c in cells
            assert m["moves"] in e2e_of[c], (m["name"], c)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_named_files_exist(bench):
    root = harness.ROOT
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((root / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
    for w in bench["workloads"]:
        spec = json.loads((harness.CHIP / "workloads"
                           / f"{w['name']}.json").read_text())
        assert (harness.CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert (harness.CHIP / "drivers" / f"{spec['driver']}.py").is_file()
    for m in bench["per_layer"]:
        assert (harness.CHIP / "metrics" / f"{m['name']}.py").is_file()
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_qwen3_config_is_the_published_one():
    cfg = json.loads((harness.CHIP / "configs"
                      / "qwen3-0.6b.json").read_text())
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"],
            cfg["vocab_size"]) == (28, 1024, 16, 8, 128, 3072, 151936)
    assert cfg["tie_word_embeddings"] and cfg["torch_dtype"] == "bfloat16"


def test_no_tpu_means_no_result(tmp_path):
    """On the CPU the command prints nothing and exits nonzero, and so it
    does from a directory holding only the benchmark's own files."""
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "qwen3-chat-shared", "--seed", "1", "--seconds", "1"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    r = subprocess.run(cmd, cwd=harness.ROOT, capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    import shutil

    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                       env=env, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
