"""The trace reduction, on a trace recorded on one TPU v5e (three steps of a
1024x1024 bf16 matmul program, a 2 ms host sleep after each, inside the
harness's window annotation) and on synthetic device lines."""
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "v5e-three-steps.xplane.pb"


def test_union_merges_overlaps_and_reports_gaps():
    busy, gaps = trace.union_length([(0, 4), (2, 6), (8, 9), (20, 30)],
                                    0, 25)
    assert busy == 6 + 1 + 5
    assert gaps == [(6, 8), (9, 20)]


def test_union_clips_to_range():
    busy, gaps = trace.union_length([(-5, 2), (3, 50)], 0, 10)
    assert busy == 2 + 7
    assert gaps == [(2, 3)]


def test_device_reduction_counts_programs_ops_and_collectives():
    ms = 1_000_000
    lines = {
        "XLA Modules": [("jit_decode(77)", 0, 10 * ms),
                        ("jit_decode(77)", 20 * ms, 30 * ms),
                        ("jit__lambda(5)", 40 * ms, 45 * ms)],
        "XLA Ops": [("%fusion.1 = bf16[8] fusion(%x)", 0, 6 * ms),
                    ("%all-gather-start.2 = (bf16[8]) all-gather-start(%y)",
                     5 * ms, 10 * ms),
                    ("%fusion.1 = bf16[8] fusion(%x)", 20 * ms, 30 * ms),
                    ("%reduce-scatter.4 = bf16[2] reduce-scatter(%z)",
                     40 * ms, 45 * ms)],
    }
    d = trace.reduce_device(lines)
    assert d.busy_s == pytest.approx(0.025)
    assert d.modules == {"decode": [0.01, 0.01], "_lambda": [0.005]}
    assert d.ops["fusion.1"] == pytest.approx(0.016)
    assert d.collectives_s == pytest.approx(0.010)
    assert d.gaps == [(10 * ms, 20 * ms), (30 * ms, 40 * ms)]


@pytest.mark.parametrize("name, collective", [
    ("all-gather-start.1", True), ("all-reduce.7", True),
    ("reduce-scatter", True), ("all-to-all.2", True),
    ("collective-permute-done", True), ("fusion.12", False),
    ("copy-start", False), ("convert_reduce_fusion", False)])
def test_collective_names(name, collective):
    assert bool(trace.COLLECTIVE.search(name)) is collective


def test_recorded_trace_summary():
    s = trace.summarize(str(FIXTURE))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.010202458)
    assert s.modules.keys() == {"_lambda"}
    assert len(s.modules["_lambda"]) == 3
    # the three programs never overlap: busy is their ops' summed time
    assert s.busy_s == pytest.approx(sum(s.ops.values()), rel=1e-6)
    assert 0 < s.busy_s < s.window_s
    assert s.collectives_s == 0.0
    assert len(s.spans["step"]) == 3 and len(s.spans["idle"]) == 3
    # the two long gaps between the programs are the host's sleeps
    assert [label for label, _ in s.idle_gaps[:2]] == ["idle", "idle"]
    assert all(g > 2e-3 for _, g in s.idle_gaps[:2])


def test_breakdown_lists_top_ops_and_gaps():
    b = trace.breakdown(trace.summarize(str(FIXTURE)), top=2)
    assert b["device_ops"][0][0] == "fusion"
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2


def test_module_names():
    assert trace.module_name("jit_decode(473504905162421246)") == "decode"
    assert trace.module_name("jit__lambda(3)") == "_lambda"
    assert trace.op_name("%fusion.3 = bf16[2] fusion(%a)") == "fusion.3"
