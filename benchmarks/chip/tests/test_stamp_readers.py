"""The readers of the engine's own request stamps (``stamps.py``): on run
records made by hand, on the request objects of an engine without the
stamps, and in a whole run on the CPU at a test size."""
import time
import types

import numpy as np
import pytest
from conftest import DATA

import harness
import manifest
import stamps

T0 = 1000.0      # the window's start on the engine's clock

READERS = ("admit_wait_p95_ms", "prefill_ms_p95", "first_token_hold_p95_ms")


def served(sid, sched, submit, stamped, t_admit, t_first, late=0.0):
    """A request as the benchmark keeps it: its own stamps from the
    window's start, and the engine's (absolute) on ``obj``; the engine
    stamped ``t_submit`` ``late`` seconds before ``submit``."""
    return types.SimpleNamespace(
        sid=sid, sched=sched, submit=submit, stamps=stamped,
        obj=types.SimpleNamespace(t_submit=T0 + submit - late,
                                  t_admit=t_admit, t_first=t_first))


def stamped_run():
    return types.SimpleNamespace(last_poll_end=12.0, requests=[
        # a new session: admitted 2 ms after submit, first token 40 ms
        # later, returned by the poll 300 ms after that
        served("a", 0.0, 0.158, [0.5, 1.0], T0 + 0.160, T0 + 0.200),
        # its next turn: first token from a step, returned 1 ms later
        served("a", 1.0, 1.0, [1.2, 1.5], T0 + 1.004, T0 + 1.199,
               late=1e-6),
        # a new session still waiting for a slot when the last poll ended
        served("b", 9.0, 9.0, [], 0.0, 0.0, late=2e-6)])


def read(name, r):
    return manifest.reader(name)(r)


def test_window_start_is_the_closest_bound():
    r = stamped_run()
    assert stamps.window_start(r.requests) == pytest.approx(T0, abs=1e-9)


def test_program_stamp_readers():
    r = stamped_run()
    # the waiting request counts to the last poll's end: 12.0 - 9.0
    assert read("admit_wait_p95_ms", r) == pytest.approx(
        np.percentile([0.002, 0.004, 3.0 + 2e-6], 95) * 1e3)
    # each session's first request only, and only once it has a token
    assert read("prefill_ms_p95", r) == pytest.approx(40.0)
    assert read("first_token_hold_p95_ms", r) == pytest.approx(
        np.percentile([0.3, 0.001], 95) * 1e3)


@pytest.mark.parametrize("name", READERS)
def test_program_stamp_readers_find_nothing_without_stamps(name):
    """An engine before the stamps: its requests have ``t_submit`` alone,
    and the readers leave the metrics out."""
    r = stamped_run()
    for q in r.requests:
        q.obj = types.SimpleNamespace(t_submit=q.obj.t_submit, t_done=0.0)
    assert read(name, r) is None
    r.requests = []
    assert read(name, r) is None


def test_engine_stamps_reach_the_readers(monkeypatch):
    """In a whole run the readers of the engine's stamps read something,
    within the window's own times."""
    records = []
    real = harness.RunRecord.__init__

    def keep(self, *a, **k):
        real(self, *a, **k)
        records.append(self)

    monkeypatch.setattr(harness.RunRecord, "__init__", keep)
    r = harness.run_cell("tiny.chat", 2**31 + 99, 4.0, False,
                         time.perf_counter(), require_tpu=False,
                         root=DATA, data=DATA)
    assert r["correct"] is True
    rec, = records
    got = {n: read(n, rec) for n in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    ttft = [1e3 * (q.stamps[0] - q.submit) for q in rec.requests
            if q.stamps]
    assert got["first_token_hold_p95_ms"] <= max(ttft) + 1.0
