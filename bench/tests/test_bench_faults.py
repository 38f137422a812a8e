"""The comparison that decides ``correct`` fails a run whose timed path
is broken underneath, once for each fault a cell can have. (The cells
run on one chip, so there is no exchange between chips to leave out.)"""
import numpy as np
import pytest

from bench.tests import tiny


def state_unchanged(fleet, tenants):
    """Every tick leaves every tenant's state as it was."""
    ingest = fleet.ingest
    fleet.ingest = lambda batch: ingest({})


def half_the_batch(fleet, tenants):
    """Half of the tenants' deltas are left out of every tick."""
    ingest = fleet.ingest
    keep = {t.name for t in tenants[::2]}
    fleet.ingest = lambda batch: ingest(
        {n: d for n, d in batch.items() if n in keep})


def answer_altered(fleet, tenants):
    """One tenant's score is altered where the fleet produces it."""
    scores = fleet.scores
    victim = tenants[len(tenants) // 2].name

    def altered(names=None):
        out = scores(names)
        out[victim] = float(np.float32(out[victim]) + np.float32(0.25))
        return out
    fleet.scores = altered


@pytest.mark.parametrize("name", ["dos.replay"])
@pytest.mark.parametrize("fault", [state_unchanged, half_the_batch,
                                   answer_altered])
def test_broken_timed_path_is_not_correct(name, fault):
    out = tiny.run(name, seconds=0.5, faults=[fault])
    assert not out["correct"], out["checks"]
