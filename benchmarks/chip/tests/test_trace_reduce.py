"""Trace reduction on a synthetic profile and on a recorded chip trace."""
import glob
import os
import types

import pytest
from conftest import HERE

import trace_reduce


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 end_ns=start + dur, duration_ns=dur)


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def profile():
    host = plane("/host:CPU", [line("main", [
        ev("bench.window", 1000, 10000),
        ev("bench.poll", 1000, 6000), ev("bench.submit", 7500, 500)])])
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_read_correct(7)", 1500, 2000),
                             ev("jit__attend_fn(9)", 4000, 1000),
                             ev("jit_other", 0, 900)]),
        line("XLA Ops", [ev("fusion.1", 1500, 2000),
                         ev("fusion.2", 4000, 1000),
                         ev("all-reduce.3", 4500, 1000),
                         ev("early", 0, 900)])])
    return types.SimpleNamespace(planes=[host, dev])


def test_reduce_synthetic_profile():
    r = trace_reduce.reduce_profile(profile())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(10000 * ns)
    # ops 1500-3500 and 4000-5500 inside the window
    assert r["busy_s"] == pytest.approx(3500 * ns)
    assert r["modules"]["jit_read_correct"] == pytest.approx(2000 * ns)
    assert "jit_other" not in r["modules"]        # outside the window
    # the all-reduce runs alone for 500 ns
    assert r["collective_exposed_s"] == pytest.approx(500 * ns)
    gaps = r["idle_gaps"]
    # 1000-1500, 3500-4000 and 5500-7000 inside the poll,
    # 7000-7500 outside any span, 7500-8000 in the submit, 8000-11000 none
    assert gaps["bench.poll"] == pytest.approx(2500 * ns)
    assert gaps["bench.none"] == pytest.approx(3500 * ns)
    assert gaps["bench.submit"] == pytest.approx(500 * ns)
    assert sum(gaps.values()) == pytest.approx(6500 * ns)
    layers = trace_reduce.layer_seconds(
        r["modules"], {"gather": ["read_correct"], "attend": ["_attend"]})
    assert layers == {"gather": pytest.approx(2000 * ns),
                      "attend": pytest.approx(1000 * ns)}


def test_reduce_refuses_a_trace_without_window_or_device():
    p = profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(p)


RECORDED = sorted(glob.glob(os.path.join(HERE, "..", "testdata",
                                         "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED)
def test_reduce_recorded_chip_trace(path):
    r = trace_reduce.reduce_file(path)
    assert r["chips"] >= 1
    assert 0 < r["busy_s"] <= r["window_s"]
    names = " ".join(r["modules"])
    assert "read_correct" in names and "_attend" in names
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_gaps"].values()) == pytest.approx(idle, rel=1e-6)
