"""The program's spans as the benchmark reads them (``bench/spans.py`` and
the four readers of ``serve.*`` spans): on a trace recorded here on the
CPU from a tiny paged batcher under the harness's annotations, and on the
v5e fixture, which holds none, like a trace of a program without them."""
import dataclasses
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import common, spans, trace
from bench.common import Run, p95

FIXTURE = Path(__file__).parent / "data" / "v5e-three-steps.xplane.pb"
READERS = ("admit_wait_p95_ms.serve", "host_ms_per_step.serve",
           "decode_row_fill.serve", "kv_fill.serve")


def tiny_batcher():
    from repro.configs import get_config
    from repro.models import Model
    from repro.serving import ContinuousBatcher
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=64,
        d_ff=64, vocab_size=32, num_heads=2, num_kv_heads=2, head_dim=16)
    model = Model(cfg)
    # 4 slots over the live-slot ladder (2, 4)
    return ContinuousBatcher(
        model, cfg, model.init(jax.random.PRNGKey(0)), slots=4, capacity=32,
        temperature=0.0, seed=0, cache_backend="paged", page_size=8,
        capture_buckets=(2, 8))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A window of the tiny batcher under the harness's annotations: five
    requests, two due before the window and three inside it. Returns the
    trace root, the batcher, its finished requests and, per decode, the
    live rows and the positions they hold, read from the batcher."""
    root = tmp_path_factory.mktemp("bench_trace")
    cb = tiny_batcher()
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, 32, size=p), g)
            for p, g in ((4, 3), (6, 7), (3, 5), (5, 4), (2, 6))]
    for prompt, g in reqs[:2]:
        cb.submit(prompt, g)
    seen = []
    decode = cb._vanilla_decode

    def spy(live_slots):
        seen.append((len(live_slots),
                     sum(int(cb.pos[s]) + 1 for s in live_slots)))
        return decode(live_slots)

    cb._vanilla_decode = spy
    done = []
    ann = jax.profiler.TraceAnnotation
    with jax.profiler.trace(str(root / "tiny-serve")):
        with ann(trace.WINDOW):
            for i in range(40):
                if 1 <= i <= 3:
                    with ann("submit"):
                        cb.submit(*reqs[i + 1])
                if not (cb.n_queued or any(cb.active)):
                    break
                with ann("step"):
                    done += cb.step()
    assert len(done) == 5
    return root, cb, done, seen


def span_run():
    """A run whose trace summary holds the window's spans (the harness's
    reduction wants a TPU plane, which a CPU trace lacks)."""
    durations = {}
    for e in spans.events():
        durations.setdefault(e.name, []).append(
            (e.end_ns - e.start_ns) * 1e-9)
    summary = trace.Summary(window_s=1.0, busy_s=0.0, busy_s_per_device=[],
                            modules={}, ops={}, collectives_s=0.0,
                            idle_gaps=[], spans=durations)
    return Run(e2e={}, checks={}, attempted=0, failed=0, peak_bytes=0,
               trace=summary)


def test_events_hold_the_batchers_counts(recorded, monkeypatch):
    root, cb, done, seen = recorded
    monkeypatch.setattr(common, "TRACE_DIR", root)
    ev = spans.events()
    assert ev and all(e.name.startswith("serve.") for e in ev)
    assert [e.start_ns for e in ev] == sorted(e.start_ns for e in ev)
    assert len(spans.events("serve.step")) == cb.steps
    # submits inside the window only
    assert [e.stats["rid"] for e in spans.events("serve.submit")] == [2, 3, 4]
    assert sorted(e.stats["rid"] for e in spans.events("serve.admit")) \
        == list(range(5))
    assert [(e.stats["live"], e.stats["kv_live"])
            for e in spans.events("serve.decode")] == seen


def test_readers_on_a_recorded_trace(recorded, monkeypatch):
    root, cb, done, seen = recorded
    monkeypatch.setattr(common, "TRACE_DIR", root)
    run = span_run()
    read = {m: common.load_reader(m)(run) for m in READERS}
    waits_ms = [(r.t_admitted - r.t_submit) * 1e3 for r in done]
    assert read["admit_wait_p95_ms.serve"] == pytest.approx(p95(waits_ms),
                                                            abs=2e-3)
    rows = [2 if live <= 2 else 4 for live, _ in seen]
    assert read["decode_row_fill.serve"] == pytest.approx(
        100 * sum(live for live, _ in seen) / sum(rows))
    assert read["kv_fill.serve"] == pytest.approx(
        100 * sum(kv for _, kv in seen) / (sum(rows) * 32))
    steps = run.trace.spans["serve.step"]
    assert len(steps) == cb.steps
    assert 0 < read["host_ms_per_step.serve"] \
        < sum(steps) / len(steps) * 1e3


@pytest.mark.parametrize("metric", READERS)
def test_reader_is_none_without_program_spans(metric, tmp_path,
                                              monkeypatch):
    shutil.copy(FIXTURE, tmp_path / FIXTURE.name)
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path)
    assert spans.events() == []
    run = Run(e2e={}, checks={}, attempted=0, failed=0, peak_bytes=0,
              trace=trace.summarize(str(FIXTURE)))
    read = common.load_reader(metric)
    assert read(run) is None
    assert read(dataclasses.replace(run, trace=None)) is None
