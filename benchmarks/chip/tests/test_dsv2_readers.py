"""The DeepSeek-V2 cell's readers: the op metadata they match by scope
or source line, on a trace recorded on a TPU v5e chip
(``testdata/probe.xplane.pb``, see ``test_trace.py``), the counter
reader, and the latent kernel's cost function."""
import pytest

from benchmarks.chip import harness
from benchmarks.chip import trace as T
from benchmarks.chip import flops_mla, xplane_scopes

DATA = harness.CHIP / "testdata" / "probe.xplane.pb"


def reader(name):
    return harness.load_file(harness.CHIP / "metrics" / f"{name}.py",
                             f"reader_{name.replace('.', '_')}")


@pytest.fixture(scope="module")
def tr():
    return T.load(str(DATA))


def test_metadata_names_scope_and_source(tr):
    meta = xplane_scopes.op_metadata(str(DATA), 0)
    assert meta is not None
    lo, hi = tr.window()
    kernel = T.op_seconds(tr, 0, lo, hi)["paged_attention"]
    by_scope = {t for t, scope, _ in meta
                if scope.startswith("jit(paged_attention)/pallas_call")}
    by_line = {t for t, _, src in meta
               if src.endswith("kernels/paged_attention.py:145")}
    assert by_scope == by_line and len(by_scope) == 1
    for texts in (by_scope, by_line):
        got = T.ops_in_module(tr, 0, lo, hi, "jit_paged_attention",
                              lambda short, text: text in texts)
        assert got == pytest.approx(kernel)
    # the matmul of the other program carries its own scope
    assert any(scope.startswith("jit(<lambda>)/dot_general")
               for _, scope, _ in meta)


def test_moe_share_reads_the_scoped_ops(tr, monkeypatch):
    """The reader, pointed at the probe's kernel program and the
    ``pallas_call`` scope: its one scoped op fills almost all of that
    program's device time; the decode program it looks for on the chip
    is absent here, so it reads nothing."""
    r = reader("moe_share.decode")
    obs = {"trace": tr, "trace_dir": str(DATA), "trace_devices": [0]}
    assert r.read(obs) is None
    monkeypatch.setattr(r, "PROGRAM", "jit_paged_attention")
    monkeypatch.setattr(r, "SCOPE", "/pallas_call")
    assert 90.0 < r.read(obs) <= 100.0


def test_expert_load_imbalance():
    r = reader("expert_load_imbalance")
    assert r.read({}) is None
    even = [[5, 5, 5, 5], [2, 2, 2, 2]]
    assert r.read({"expert_tokens": even}) == pytest.approx(1.0)
    # one layer all on one expert (4x the mean), a dense layer skipped
    skewed = [[0, 0, 0, 0], [8, 0, 0, 0], [3, 1, 2, 2]]
    assert r.read({"expert_tokens": skewed}) == pytest.approx((4 + 1.5) / 2)


def test_latent_cost_counts_whole_pages():
    cfg = {"num_attention_heads": 16, "kv_lora_rank": 512,
           "qk_rope_head_dim": 64}
    c = flops_mla.latent_attention_cost(cfg, [17, 32], 16)
    # 2 + 2 pages of 16 tokens at 576 x 2 B, the f32 query and output
    assert c["bytes"] == 4 * 16 * 576 * 2 + 2 * 16 * (576 + 512) * 4
    assert c["flops"] == 2 * 16 * (17 + 32) * (576 + 512)
