"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e by ``record_trace.py``."""

import os

import pytest

from benchlib.trace import (NO_HOST_EVENT, _label_gaps, host_timeline,
                            idle_gaps, reduce_trace, union_length)

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


def test_union_counts_overlaps_once_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26)]
    assert union_length(iv) == 25
    assert union_length(iv, 8, 22) == 9


def test_idle_gaps_are_the_uncovered_stretches():
    assert idle_gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8),
                                                           (9, 10)]
    assert idle_gaps([], 0, 5) == [(0, 5)]
    assert idle_gaps([(0, 5)], 0, 5) == []


def test_a_trace_whose_device_events_stop_early_is_refused(monkeypatch):
    """A profiler whose device buffer filled drops the window's later
    operations: the reduction refuses the trace instead of reading it."""
    import types

    import jax.profiler

    def plane(name, line, events):
        evs = [types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
               for n, s, d in events]
        return types.SimpleNamespace(
            name=name, lines=[types.SimpleNamespace(name=line, events=evs)])

    pd = types.SimpleNamespace(planes=[
        plane("/device:TPU:0", "XLA Ops", [("op", 0, 1e8)]),
        plane("/host:CPU", "main", [("bench.window", 0, 2e9)])])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: pd)
    with pytest.raises(ValueError, match="incomplete"):
        reduce_trace("recorded.xplane.pb")


def test_gaps_are_split_by_the_latest_begun_host_event():
    host = [(0, 10, "a"), (2, 4, "b"), (3, 8, "c"), (12, 14, "d")]
    assert host_timeline(host) == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"),
                                   (4, 8, "c"), (8, 10, "a"), (12, 14, "d")]
    assert _label_gaps([(1, 5), (9, 13)], host) == [
        (1, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "c"), (9, 10, "a"),
        (10, 12, NO_HOST_EVENT), (12, 13, "d")]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_chip_trace():
    r = reduce_trace(DATA)
    assert r["devices"] == 1
    # three steps of one program in a window with 2 ms host pauses
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] > 3 * 0.002
    assert 0 < r["idle_share"] < 1
    assert abs(r["idle_share"] - (1 - r["busy_s"] / r["window_s"])) < 1e-12
    gaps = dict(r["idle_gaps"])
    # the device idles longest through the host's three annotated 2 ms
    # pauses, then while the host waits on each step's launch and completion
    assert r["idle_gaps"][0][0] == "bench.host"
    assert 3 * 0.002 <= gaps["bench.host"] < 3 * 0.003
    assert r["idle_gaps"][1][0] == "bench.sync"
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
