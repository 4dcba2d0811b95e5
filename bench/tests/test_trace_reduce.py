"""The trace reduction: busy union, idle share, gap attribution.

One hand-worked case, and a small trace recorded on one TPU v5e (a short
window of the test-size sweep: the device's op events, the window mark
and the program's host spans on the profiler's clock).
"""

import json
import os

import pytest

from bench.lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def sweep_busy(intervals, lo, hi):
    """Busy time by an event sweep (a second algorithm for the union)."""
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e in intervals
                      if e > lo and s < hi])
    busy, depth, t = 0.0, 0, lo
    for x, d in edges:
        if depth > 0:
            busy += x - t
        depth += d
        t = x
    return busy


def test_hand_worked_window():
    # busy [0, 15] and [40, 45]; idle [15, 40] and [45, 50]
    trace = {"devices": {"/device:TPU:0": [("a", 0.0, 10.0), ("b", 5.0, 10.0),
                                           ("c", 40.0, 5.0)]}}
    spans = [("host.prep", 12.0, 32.0), ("host.eval", 34.0, 50.0),
             ("outer", 0.0, 50.0)]
    r = tr.reduce(trace, spans, 0.0, 50.0)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["window_s"] == pytest.approx(50e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"a": 10e-9, "b": 10e-9, "c": 5e-9})
    # [15, 32] prep, [32, 34] only the outer span, [34, 40] + [45, 50] eval
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"host.prep": 17e-9, "outer": 2e-9, "host.eval": 11e-9})


def test_union_and_gaps():
    ivs = tr.union([(5, 9), (0, 2), (1, 3), (9, 10), (20, 25)])
    assert ivs == [(0, 3), (5, 10), (20, 25)]
    assert tr.gaps(tr.clip(ivs, 1, 22), 1, 30) == [(3, 5), (10, 20), (22, 30)]
    assert tr.timeline([], 0, 5) == [(0, 5, "no span")]
    assert tr.timeline([("x", 1, 2)], 0, 5) == [
        (0, 1, "no span"), (1, 2, "x"), (2, 5, "no span")]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return json.load(f)


def test_recorded_trace(recorded):
    lo, hi = recorded["window"]
    events = recorded["devices"]
    spans = [tuple(s) for s in recorded["spans"]]
    r = tr.reduce({"devices": events}, spans, lo, hi)
    ivs = [(s, s + d) for evs in events.values() for _, s, d in evs]
    busy = sweep_busy(ivs, lo, hi) / len(events)
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(
        r["window_s"], rel=1e-9)
    assert set(idle) <= {s[0] for s in spans} | {"no span"}
    # the same attribution sampled on a grid: at each idle instant, the
    # covering span that opened last
    grid = 20000
    step = (hi - lo) / grid
    busy_ivs = tr.union(tr.clip(ivs, lo, hi))
    want: dict = {}
    for i in range(grid):
        t = lo + (i + 0.5) * step
        if any(s <= t < e for s, e in busy_ivs):
            continue
        cover = [sp for sp in spans if sp[1] <= t < sp[2]]
        name = max(cover, key=lambda sp: sp[1])[0] if cover else "no span"
        want[name] = want.get(name, 0.0) + step / 1e9
    for name, v in want.items():
        assert idle.get(name, 0.0) == pytest.approx(v, abs=50 * step / 1e9)
