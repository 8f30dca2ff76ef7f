"""The ``kv_read_share.serve`` reader: on a trace recorded here on the CPU
from a tiny paged batcher, whose decode takes the gather path and reads all
it gathers, and on the v5e fixture, whose program wrote no spans."""
import dataclasses
import shutil

import pytest

from bench import common, spans, trace
from bench.common import Run
from bench.tests.test_spans import FIXTURE, recorded, span_run  # noqa: F401

READ = common.load_reader("kv_read_share.serve")


def test_reads_the_decode_spans(recorded, monkeypatch):  # noqa: F811
    root, cb, done, seen = recorded
    monkeypatch.setattr(common, "TRACE_DIR", root)
    dec = spans.events("serve.decode")
    assert dec and all(e.stats["kv_read"] == e.stats["kv_gathered"]
                       for e in dec)
    assert READ(span_run()) == pytest.approx(100.0)


def test_is_none_without_the_stat(tmp_path, monkeypatch):
    shutil.copy(FIXTURE, tmp_path / FIXTURE.name)
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path)
    run = Run(e2e={}, checks={}, attempted=0, failed=0, peak_bytes=0,
              trace=trace.summarize(str(FIXTURE)))
    assert READ(run) is None
    assert READ(dataclasses.replace(run, trace=None)) is None


def test_is_none_on_spans_without_kv_read(monkeypatch):
    """A program whose ``serve.decode`` spans predate the stat."""
    ev = spans.Event("serve.decode", 0.0, 1.0,
                     {"rows": 4, "live": 2, "kv_gathered": 128, "kv_live": 9})
    monkeypatch.setattr(spans, "events", lambda name=None: [ev])
    assert READ(span_run()) is None
