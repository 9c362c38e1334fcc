"""A whole run of a cell with its look for a chip skipped, at a size a
CPU holds: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.

The faults a serving cell can have: half of each fused batch left out
(its rows answered with the other half's answers), an answer altered
where it is produced (one id of each fused call), and a batch's answers
scattered back to the wrong requests.  A fault in the scan that only
worsens the shortlist shows in ``recall_loss``, as a kernel that
scores at random does."""
import argparse
import time

import numpy as np
import pytest

from bench import harness
from repro.index import api

SMALL = {
    "config": {"n_rows": 6000,
               "index": {"n_landmarks": 32, "landmark_sample": 4096}},
    "traffic": {"pool_rows": 2048, "clients": 4, "rate_rps": 150},
}


def _run(workload, seed=5):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=1.5,
                              trace=0)
    return harness.run(args, t_process=time.perf_counter(),
                       require_tpu=False, overrides=SMALL)


def _break(monkeypatch, fault):
    search = api.AshIndex.search_prepped

    def broken(self, prep, k=10, **kw):
        s, i = search(self, prep, k, **kw)
        s, i = np.array(s), np.array(i)
        m = len(i)
        if fault == "half_left_out":
            # the engine pads a batch with zero rows: leave out the
            # last half (rounded up) of the real rows, answering them
            # with what the batch computed for another row
            real = int(np.abs(np.asarray(prep.q)).sum(1).astype(bool).sum())
            cut = real - (real + 1) // 2
            src = real if real < m else 0
            s[cut:real], i[cut:real] = s[src], i[src]
        elif fault == "answer_altered":
            i[0, 0] = (i[0, 0] + 1) % self.n
        elif fault == "misrouted":
            s, i = np.roll(s, 1, axis=0), np.roll(i, 1, axis=0)
        elif fault == "random_scan":
            rng = np.random.default_rng(m)
            i = np.sort(rng.choice(self.n, size=(m, i.shape[1])), axis=1)
        return s, i

    monkeypatch.setattr(api.AshIndex, "search_prepped", broken)


CELLS = ["sift-flat-batch32-closed", "glove-ivf-single-open"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize(
    "fault", ["half_left_out", "answer_altered", "misrouted", "random_scan"]
)
def test_broken_path_is_not_correct(monkeypatch, workload, fault):
    _break(monkeypatch, fault)
    res = _run(workload)
    assert not res["correct"], (fault, res["checks"])
