"""The whole run on the CPU at a test size: sound runs come out correct,
the timed path broken underneath comes out not correct, and the int8
control fails the limit the program meets. Only the look for a chip is
skipped (``require_tpu=False``)."""
import time

import pytest
from conftest import DATA

import harness


def run(cell, seconds=4.0, **kw):
    return harness.run_cell(cell, 2**31 + 99, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            root=DATA, data=DATA, **kw)


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.complete",
                                  "tiny.overcommit"])
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"tok_s", "itl_p95_ms", "setup_s"} <= set(r["metrics"])
    assert list(r)[-1] == "checks"
    assert r["checks"]["tokens_compared"]["value"] >= 20


def test_refuses_without_a_tpu():
    with pytest.raises(harness.NoChip):
        harness.run_cell("tiny.chat", 1, 1.0, False, time.perf_counter(),
                         root=DATA, data=DATA)


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve import engine
    real = engine.Engine.step

    def step(self):
        done = real(self)
        s = self.sched.slots[0]
        if s is not None and s.req.generated:
            s.req.generated[-1] = (s.req.generated[-1] + 1) \
                % self.cfg.vocab_size
        for r in done:                     # finished in this step
            r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab_size
        return done

    monkeypatch.setattr(engine.Engine, "step", step)
    r = run("tiny.chat")
    assert r["correct"] is False
    assert r["checks"]["mean_gap"]["value"] > \
        r["checks"]["mean_gap"]["limit"]


def test_step_that_leaves_the_kv_unchanged_is_not_correct(monkeypatch):
    """A decode step whose page write is lost: the pool comes out of the
    step as it went in, so later tokens attend to stale pages."""
    from repro.serve import engine
    real = engine.Engine.step

    def step(self):
        import dataclasses
        import jax.numpy as jnp
        before = jnp.copy(self.pool.storage)   # the write donates it
        done = real(self)
        self.vm.pools[self.pool_name] = dataclasses.replace(
            self.pool, storage=before)
        return done

    monkeypatch.setattr(engine.Engine, "step", step)
    r = run("tiny.chat")
    assert r["correct"] is False


def test_uncorrected_flip_is_not_correct(monkeypatch):
    """Two bits flipped in one word of the paid page: SECDED detects them
    but cannot correct them, so what is served on top of the page departs
    from the reference, as it would if the gather skipped correction."""
    monkeypatch.setattr(harness, "FLIP_BITS", (30, 22))
    r = run("tiny.chat")
    assert r["correct"] is False


def test_int8_control_fails_the_limit():
    r = run("tiny.overcommit", control=True)
    assert r["correct"] is True
    # the control, put in the program's place, reads above the limit
    assert r["control_mean_gap"] > r["checks"]["mean_gap"]["limit"]
