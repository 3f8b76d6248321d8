"""Two seeds offer the same work (the same multiset of sizes and of
gaps between arrivals) at arrival times of their own; one seed gives
the same plan twice."""

import json
import os

import numpy as np

import traffic

CELLS = os.path.join(os.path.dirname(__file__), "cells")


def plan(seed):
    with open(os.path.join(CELLS, "gpt2.chat_tiny.json")) as f:
        spec = json.load(f)["traffic"]
    return traffic.request_plan(spec, seed, 20.0)


def test_seeds_share_the_work_and_not_the_arrival_times():
    a, b = plan(3000000019), plan(77)
    assert plan(77) == b
    for key in ("n_new",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    ramp = [r for r in a if r["due"] < 1.0]
    due_a = np.array([r["due"] for r in a[len(ramp):]])
    due_b = np.array([r["due"] for r in b[len(ramp):]])
    assert len(due_a) == len(due_b) and not np.allclose(due_a, due_b)
    # the first request is due at the window's opening, so the gap
    # dealt to it shows as the room left at the window's end
    gaps = lambda due: np.sort(np.diff(np.append(due, 1.0 + 20.0)))
    assert np.allclose(gaps(due_a), gaps(due_b))
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
