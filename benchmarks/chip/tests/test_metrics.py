"""Each metric reader on a run record made by hand."""
import math
import types

import pytest

import counts
import manifest


def req(sched, stamps, submit=None, first_poll=None):
    return types.SimpleNamespace(
        sched=sched, stamps=stamps,
        submit=sched if submit is None else submit,
        first_poll=math.nan if first_poll is None else first_poll)


M = {"L": 2, "d": 4, "hq": 2, "hkv": 1, "hd": 2, "ff": 8, "V": 10}


def run(**kw):
    base = dict(
        loop="open", window_s=10.0, setup_s=12.5, chips=1, max_batch=4,
        requests=[req(0.0, [0.5, 1.0, 1.5], submit=0.1, first_poll=0.2),
                  req(2.0, [3.0, 3.5, 11.0], submit=2.4, first_poll=2.5),
                  req(9.0, [], submit=9.2)],
        last_poll_end=12.0,
        polls=[(0.0, 2.0), (2.5, 5.0), (9.5, 12.0)],
        steps=[{"lens": [10, 20], "secded_pages": 2},
               {"lens": [11, 21, 5], "secded_pages": 3}],
        prefills=[16, 32], swap_pages={"out": 30, "in": 20},
        dims=M, counts=counts, block_tokens=8, page_bytes=64,
        code_bytes=8,
        peaks={"flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
        trace={"window_s": 10.0, "busy_s": 7.5,
               "modules": {"jit_read_correct": 0.02, "jit__attend_fn": 0.5,
                           "jit_write_pages_any": 0.01}},
        layers={"gather": ["read_correct"], "attend": ["_attend"],
                "scatter": ["write_pages_any"]})
    base.update(kw)
    r = types.SimpleNamespace(**base)
    r.layer_s = lambda layer: sum(
        v for k, v in r.trace["modules"].items()
        if any(p in k for p in r.layers[layer]))
    return r


def read(name, r):
    return manifest.reader(name)(r)


def test_end_to_end_readers():
    r = run()
    assert read("tok_s", r) == pytest.approx(5 / 10.0)
    # ttft: 0.5, 1.0, and a request still waiting at 12.0 (due at 9.0)
    import numpy as np
    assert read("ttft_p95_ms", r) == pytest.approx(
        np.percentile([0.5, 1.0, 3.0], 95) * 1e3)
    assert read("itl_p95_ms", r) == pytest.approx(
        np.percentile([0.5, 0.5, 0.5], 95) * 1e3)
    assert read("setup_s", r) == 12.5
    assert read("ttft_p95_ms", run(loop="closed")) is None


def test_host_per_layer_readers():
    import numpy as np
    r = run()
    assert read("gen_lag_p95_ms", r) == pytest.approx(
        np.percentile([0.1, 0.4, 0.2], 95) * 1e3)
    assert read("queue_p95_ms", r) == pytest.approx(
        np.percentile([0.2, 0.5], 95) * 1e3)
    assert read("batch_occupancy_pct", r) == pytest.approx(100 * 5 / 8)
    assert read("swap_pages_s", r) == pytest.approx(5.0)


def test_trace_readers():
    r = run()
    assert read("idle_pct", r) == pytest.approx(25.0)
    assert read("gather_ms_per_step", r) == pytest.approx(1e3 * 0.02 / 2)
    # live blocks with 8-token blocks: (10,20) -> 2+3, (11,21,5) -> 2+3+1
    need = (5 * 2 * 2 * 64 + 2 * 8) + (6 * 2 * 2 * 64 + 3 * 8)
    assert read("gather_roofline", r) == pytest.approx(
        100 * need / (0.02 * 1e5))
    least = 0.0
    for lens in ([10, 20], [11, 21, 5]):
        f, b = counts.attend_step(M, lens)
        least += max(f / 1e6, b / 1e5)
    assert read("attend_roofline", r) == pytest.approx(100 * least / 0.5)
    flops = sum(counts.prefill_flops(M, n) for n in (16, 32)) + sum(
        counts.decode_flops(M, n + 1) for n in (10, 20, 11, 21, 5))
    # the polls that started in the window took 2 + 2.5 + 2.5 s
    assert read("mfu_pct", r) == pytest.approx(100 * flops / (7 * 1e6))


def test_trace_readers_find_nothing_without_a_trace():
    r = run(trace=None)
    for name in ("idle_pct", "gather_ms_per_step", "gather_roofline",
                 "attend_roofline"):
        assert read(name, r) is None


def test_a_layer_no_traced_program_matches_is_an_error():
    import harness
    r = run()
    real = types.SimpleNamespace(trace=r.trace, layers=r.layers)
    assert harness.RunRecord.layer_s(real, "gather") == pytest.approx(0.02)
    real.layers = {**r.layers, "gather": ["renamed_gather"]}
    with pytest.raises(RuntimeError, match="gather"):
        harness.RunRecord.layer_s(real, "gather")
