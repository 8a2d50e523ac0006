"""The comparison that decides ``correct`` must fail on the control and
on each fault a serving cell can have, planted under the timed path.

The control is the program with its exactness path switched off
(``run.CONTROL``: no escalation ladder, no CPU net).  At this size it
needs windows that meet more pages than its candidate budget to have
anything to get wrong, so its runs widen the windows to a fifth of the
domain and set ``max_cand`` to 4 and ``max_hits`` to 64; the sound
program, run the same way, escalates past them and stays correct."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

SMALL_BUDGETS = {"max_cand": 4, "max_hits": 64}
WIDE = {"count_width": 0.2, "range_width": 0.1}
CELL = next(c for c in tiny.cells() if c["traffic"].endswith("open")
            and "store" not in c["config"])


def _alter_one_answer(system):
    """An answer altered where it is produced: every Count batch comes
    back with its first count one too high."""
    db = system.db
    query = db.query

    def altered(q, *a, **kw):
        res = query(q, *a, **kw)
        if q.kind == "count":
            res.counts = res.counts.copy()
            res.counts[0] += 1
        return res

    db.query = altered


def _drop_half_the_batch(system):
    """Half of each coalesced batch left out: the second half of every
    Count / Point batch answers as if it matched nothing."""
    db = system.db
    query = db.query

    def halved(q, *a, **kw):
        res = query(q, *a, **kw)
        if q.kind == "count":
            res.counts = res.counts.copy()
            res.counts[len(res.counts) // 2:] = 0
        elif q.kind == "point":
            res.found = res.found.copy()
            res.found[len(res.found) // 2:] = False
        return res

    db.query = halved


@pytest.mark.parametrize("fault,expect", [
    ("none_small_budgets", True),
    ("control", False),
    ("altered", False),
    ("half_batch", False),
])
def test_fault_is_caught(fault, expect, tmp_path):
    import run
    kw = {}
    if fault in ("none_small_budgets", "control"):
        kw.update(engine=SMALL_BUDGETS, mix=WIDE)
    if fault == "control":
        kw["engine_overrides"] = run.CONTROL
    elif fault == "altered":
        kw["after_build"] = _alter_one_answer
    elif fault == "half_batch":
        kw["after_build"] = _drop_half_the_batch
    out = tiny.run_tiny(CELL, tmp_path, **kw)
    assert out["correct"] is expect, out["_log"]
    bad = out["compared"]["mismatched"]["value"]
    assert (bad == 0) is expect
    assert out["compared"]["mismatched"]["limit"] == 0
