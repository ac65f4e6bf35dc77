"""Drive whole runs with the chip check skipped, at a small size on the
CPU: a sound run is correct, and each fault a one-chip cell can have,
planted in the timed path, makes ``correct`` false.  (The exchange
between chips does not exist on one chip.)"""
import time

import numpy as np
import pytest

from perfbench.harness.cell import run_cell
from perfbench.tests.conftest import SMALL
from repro.api import session as api_session
from repro.core import incremental
from repro.stream import session as stream_session


def unchanged(monkeypatch):
    """A refresh that returns its state unchanged."""
    monkeypatch.setattr(api_session._OneStepMRBG, "update",
                        lambda self, delta: None)


def half_batch(monkeypatch):
    """Half of each micro-batch's events left out."""
    real = stream_session.coalesce
    monkeypatch.setattr(stream_session, "coalesce",
                        lambda records, **kw: real(records[::2], **kw))


def altered(monkeypatch):
    """One answer altered where the result is produced."""
    as_dict = incremental.ResultView.as_dict

    def bump(out):
        for a in out.values():
            a[0] += 1
        return out
    monkeypatch.setattr(incremental.ResultView, "as_dict",
                        lambda self: bump(as_dict(self)))


def run(cell, seed=2**31 + 99):
    return run_cell(cell, seed, 2.0, False, t_start=time.perf_counter(),
                    require_chip=False, overrides=SMALL[cell],
                    log=lambda s: None)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s", "delta_rows_per_s"}


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered])
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_fault_is_caught(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run(cell)
    assert not out["correct"], out["checks"]
