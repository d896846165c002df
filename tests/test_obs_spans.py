"""The tracing plane on the profiler's clock: spans as profiler
annotations, the serving engine's span tree, and the engine's always-on
request stamps and gather counters."""
import contextlib
import glob
import os
import types

import jax
import jax.numpy as jnp
import pytest

from repro.obs import tracing


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing off and empty."""
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@contextlib.contextmanager
def _profiled(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _xplane_spans(log_dir, names) -> dict:
    """Host events of the given names in the session's ``.xplane.pb``, by
    name, each with ``start_ns``, ``end_ns``, ``line`` and ``stats``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(types.SimpleNamespace(
                        start_ns=ev.start_ns, end_ns=ev.end_ns,
                        line=(plane.name, line.name),
                        stats=dict(list(ev.stats))))
    return out


def test_span_lands_in_the_profiler_trace(tmp_path):
    """An enabled span is also a profiler annotation: it shows in the
    ``.xplane.pb`` with its arguments, on the profiler's clock."""
    tracing.enable()
    with _profiled(tmp_path):
        with tracing.span("outer", seq_id="s7", pages=3):
            with tracing.span("inner"):
                pass
    evs = _xplane_spans(tmp_path, {"outer", "inner"})
    (outer,), (inner,) = evs["outer"], evs["inner"]
    assert outer.stats["seq_id"] == "s7" and outer.stats["pages"] == 3
    assert outer.start_ns <= inner.start_ns
    assert inner.end_ns <= outer.end_ns
    assert {"outer", "inner"} <= tracing.TRACER.span_names()


def test_disabled_span_writes_nothing_to_the_profiler(tmp_path):
    with _profiled(tmp_path):
        with tracing.span("quiet"):
            pass
    assert _xplane_spans(tmp_path, {"quiet"}) == {}
    assert tracing.TRACER.events == []


def _tiny_engine(**kw):
    from benchmarks.bench_serving import CFG
    from repro.serve.engine import Engine
    return Engine(CFG, max_batch=2, max_len=24, num_rows=32, row_words=64,
                  secded_rows=8, **kw)


def _tiny_requests(n=2, max_new=3):
    from repro.serve.engine import Request
    return [Request(f"s{i}", list(range(1, 7)), max_new,
                    tier="paid" if i % 2 else "batch") for i in range(n)]


#: The engine's span tree on a local pool: child -> parent.
ENGINE_SPANS = {
    "engine.poll": None,
    "sched.tick": "engine.poll",
    "engine.prefill": "engine.poll",
    "engine.step": "engine.poll",
    "engine.step.plan": "engine.step",
    "engine.step.gather": "engine.step",
    "engine.step.compute": "engine.step",
    "engine.step.scatter": "engine.step",
    "engine.step.sync": "engine.step",
    "engine.step.emit": "engine.step",
}


def _serve_turns(eng, turns=2, max_new=4):
    """Two sessions, ``turns`` turns each (a fresh turn, then continuations
    on the parked KV); returns every request, in submission order."""
    reqs = []
    for _ in range(turns):
        batch = _tiny_requests(n=2, max_new=max_new)
        for r in batch:
            eng.submit(r)
        while eng.sched.has_work():
            eng.poll()
        reqs += batch
    return reqs


def test_engine_spans_nest_under_poll_in_the_profiler_trace(tmp_path):
    tracing.enable()
    eng = _tiny_engine()
    with _profiled(tmp_path):
        _serve_turns(eng)
    evs = _xplane_spans(tmp_path, set(ENGINE_SPANS))
    assert set(evs) == set(ENGINE_SPANS)
    for name, parent in ENGINE_SPANS.items():
        if parent is None:
            continue
        for e in evs[name]:
            assert any(p.line == e.line and p.start_ns <= e.start_ns
                       and e.end_ns <= p.end_ns
                       for p in evs[parent]), \
                f"{name} lies outside any {parent}"
    # two fresh sessions prefill, each with its own seq_id
    assert sorted(e.stats["seq_id"] for e in evs["engine.prefill"]) \
        == ["s0", "s1"]
    assert {e.stats["tier"] for e in evs["engine.prefill"]} == \
        {"paid", "batch"}


def test_tracing_on_never_waits_for_the_device(monkeypatch):
    """With tracing on the engine makes no extra sync: it serves with
    ``block_until_ready`` gone, and serves the same tokens."""
    plain = [r.generated for r in _serve_turns(_tiny_engine())]

    def refuse(*a, **k):
        raise AssertionError("block_until_ready called while serving")

    tracing.enable()
    eng = _tiny_engine()
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    monkeypatch.setattr(type(jnp.zeros(1)), "block_until_ready", refuse)
    traced = [r.generated for r in _serve_turns(eng)]
    assert traced == plain
    assert "engine.step.sync" in tracing.TRACER.span_names()


def test_request_stamps_split_the_first_token():
    """``t_admit`` when a tick binds the request, ``t_first`` when its
    first token is on the host: after the prefill for a fresh
    session, after the first step for a continuation."""
    eng = _tiny_engine()
    fresh0, fresh1, cont0, cont1 = _serve_turns(eng)
    for r in (fresh0, fresh1, cont0, cont1):
        assert 0 < r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    # tracing was off: the stamps are always on
    assert tracing.TRACER.events == []
    # a continuation's first token comes from a decode step, so no
    # earlier than the fresh turns finished
    assert cont0.t_first >= max(fresh0.t_done, fresh1.t_done)


def test_gather_page_counters():
    """Every step gathers the whole padded table; the live pages are
    the blocks ``< ceil((len + 1) / block_tokens)`` of bound slots."""
    eng = _tiny_engine()
    lens_seen = []
    real = eng.kv.gather_phys

    def spy(rows):
        if len(rows) == eng.max_batch:          # the step's lookup
            lens_seen.append([eng.sched.slots[i].cache_len
                              for i in range(eng.max_batch)
                              if rows[i] >= 0])
        return real(rows)

    eng.kv.gather_phys = spy
    _serve_turns(eng)
    per_step = eng.max_batch * eng.n_layers * eng.kv.max_blocks
    assert eng.steps == len(lens_seen) > 0
    assert eng.pages_gathered == eng.steps * per_step
    bt = eng.kv.block_tokens
    assert eng.pages_gathered_live == eng.n_layers * sum(
        -(-(n + 1) // bt) for lens in lens_seen for n in lens)
    assert 0 < eng.pages_gathered_live < eng.pages_gathered


def test_gather_secded_counter():
    """``pages_gathered_secded`` counts the gathered ids in the SECDED
    region ``[boundary, num_rows)``: the paid session's live pages, and
    nothing of the CREAM batch session or the padding."""
    eng = _tiny_engine()
    expect = []
    real = eng.kv.gather_phys

    def spy(rows):
        phys = real(rows)
        if len(rows) == eng.max_batch:          # the step's lookup
            pool = eng.pool
            expect.append(int(((phys >= pool.boundary)
                               & (phys < pool.num_rows)).sum()))
        return phys

    eng.kv.gather_phys = spy
    _serve_turns(eng)
    assert eng.steps == len(expect) > 0
    assert eng.pages_gathered_secded == sum(expect)
    assert 0 < eng.pages_gathered_secded < eng.pages_gathered_live
