"""``correct`` at a size the CPU holds: a clean run passes, and the control
and every fault a serving cell can have come out not correct. The run is
driven as on the chip (``bench.run.execute``), past the chip check only.

The tiny cell's limit on ``served_mean_gap``, 4e-5, lies between what
clean runs read (CPU, seeds 1-8 and 11, 3 s windows after a 1.5 s
pre-warm, the sample drawn from the requests that finished inside the
window: at most 2.4e-5; 1.6e-5, 1.0e-5, 2.3e-5, 1.2e-6, 7.7e-6, 0,
2.4e-5, 0, 3.6e-6) and what the float8 control reads on the same samples
(at least 1.3e-4; 1.3e-4, 3.6e-4, 3.9e-4, 2.8e-4, 3.4e-4, 1.8e-4,
3.1e-4, 1.4e-4, 7.6e-4). The tiny cell is offered more than it serves;
nothing here is a measurement.
"""
import json

import jax
import numpy as np
import pytest

from bench import run as harness
from bench.peaks import PEAKS
from bench.tests.tiny import serve_cell

LIMIT = 4e-5
V5E = PEAKS["TPU v5 lite"]


def execute(seed: int):
    out, run, _ = harness.execute(serve_cell(LIMIT), seed, 3.0, False, jax,
                                  jax.devices()[:1], V5E)
    return out, run


@pytest.fixture(scope="module")
def clean():
    return harness.execute(serve_cell(LIMIT), 11, 3.0, False, jax,
                           jax.devices()[:1], V5E)


def test_clean_run_is_correct(clean):
    out, run, _ = clean
    assert out["correct"], out["checks"]
    assert run.counters["checked_tokens"] > 100
    assert out["attempted"] > 0 and out["failed"] == 0


def test_result_lines(clean, capsys):
    """The result is the last line of standard output, its ``checks`` key
    comes last, and each number compared is the last line of standard
    error beside its limit."""
    out, run, ctx = clean
    harness.report(serve_cell(LIMIT), out, run, ctx)
    stdout, stderr = capsys.readouterr()
    result = json.loads(stdout.strip().splitlines()[-1])
    assert list(result)[:3] == ["correct", "attempted", "failed"]
    assert list(result)[-1] == "checks"
    assert {"metrics", "device"} <= set(result)
    value = result["checks"]["served_mean_gap"]["value"]
    assert stderr.strip().splitlines()[-1] == \
        f"check served_mean_gap {value!r} limit {LIMIT!r} ok"
    assert "programs traced inside the window 0" in stderr


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(seed):
    """The reference in float8, in the program's place: at every position
    of the served requests, the gap of the token it puts first."""
    out, run, _ = harness.execute(serve_cell(LIMIT), seed, 3.0, False, jax,
                                  jax.devices()[:1], V5E, control="fp8")
    assert not out["correct"], out["checks"]
    assert run.counters["program_gap"]["mean"] <= LIMIT


def _alter_tokens(monkeypatch):
    """A served token altered where it is produced."""
    from repro.serving.scheduler import ContinuousBatcher
    orig = ContinuousBatcher._append_emitted

    def altered(self, s, toks):
        return orig(self, s, [(int(t) + 1) % self.cfg.vocab_size
                              for t in toks])
    monkeypatch.setattr(ContinuousBatcher, "_append_emitted", altered)


def _drop_decode_state(monkeypatch):
    """A decode step that returns its KV state unchanged."""
    from repro.models import Model
    orig = Model.paged_decode_step

    def unchanged(self, params, pools, *args, **kw):
        logits, _ = orig(self, params, pools, *args, **kw)
        return logits, pools
    monkeypatch.setattr(Model, "paged_decode_step", unchanged)


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_decode_state],
                         ids=["token_altered", "decode_state_unchanged"])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out, run = execute(11)
    assert not out["correct"], out["checks"]
    value = out["checks"]["served_mean_gap"]["value"]
    assert np.isfinite(value) and value > 10 * LIMIT
