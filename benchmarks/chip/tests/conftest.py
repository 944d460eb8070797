"""The benchmark's tests run on the CPU, with four virtual devices for
the LTFB mesh: ``python -m pytest benchmarks/chip/tests``."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")


# the LTFB cell's manifest entries, for its CPU tests: the cell is not in
# ``BENCHMARK.json`` until it is proved on four chips
LTFB_ENTRIES = {
    "configs": [{"name": "icf-cyclegan",
                 "file": "benchmarks/chip/configs/icf-cyclegan.json",
                 "reduced": []}],
    "workloads": [{"name": "icf-ltfb-k4-4chip", "config": "icf-cyclegan",
                   "traffic": "ltfb-k4", "chips": 4}],
    "end_to_end": [{"name": "train_samples_per_s", "unit": "samples/s",
                    "workloads": ["icf-ltfb-k4-4chip"]}],
    "per_layer": [{"name": n, "unit": "%", "moves": "train_samples_per_s",
                   "workloads": ["icf-ltfb-k4-4chip"]}
                  for n in ("tournament_share.ltfb", "data_wait_share.ltfb",
                            "mfu.train", "idle_share.train")],
}


@pytest.fixture
def ltfb_listed(monkeypatch):
    """``BENCHMARK.json`` with the LTFB cell listed."""
    from benchmarks.chip import harness

    real = harness.load_manifest
    extra = LTFB_ENTRIES

    def load(root=harness.ROOT):
        bench = real(root)
        if all(w["name"] != "icf-ltfb-k4-4chip" for w in bench["workloads"]):
            for key, entries in extra.items():
                bench[key] = bench[key] + entries
        return bench

    monkeypatch.setattr(harness, "load_manifest", load)
