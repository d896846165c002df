"""CREAM-Serve acceptance: paged-KV decode parity and preempt-to-host.

The paged engine's whole value rests on two claims:

  * the paged read path (one batched pool gather per decode step, on local
    or sharded pools, in CREAM or SECDED mode) produces *exactly* the
    tokens the dense-KV decode path produces;
  * preempting a sequence's KV to the host tier — by capacity pressure or
    by a mid-decode repartition that shrinks the weak-class pool — and
    resuming it later is bit-exact (same tokens as an unpreempted run).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.layouts import Layout
from repro.core.pool import PoolState
from repro.core.protection import Protection
from repro.models import transformer
from repro.serve import Engine, ServeRequest
from repro.vm.address_space import VirtualMemory
from repro.vm.migration import MigrationEngine

CFG = ModelConfig(name="serve-test", family="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=256, head_dim=16, dtype="float32")


def _prompts(n, rng=None):
    rng = rng or np.random.default_rng(1)
    return [rng.integers(0, 256, size=12).astype(np.int32)
            for _ in range(n)]


def _reqs(prompts, max_new=8):
    return [ServeRequest(f"s{i}", p, max_new)
            for i, p in enumerate(prompts)]


def _dense_reference(eng, prompts, max_new=8):
    """Greedy decode each prompt with the dense decode_step path."""
    model, params = eng.model, eng.params
    step = jax.jit(model.decode_step)
    pre = jax.jit(lambda p, t: model.prefill(p, t, eng.max_len))
    out = []
    for p in prompts:
        logits, state = pre(params, jnp.asarray(p[None, :], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        gen = [tok]
        for _ in range(max_new - 1):
            lg, state = step(params, state, jnp.asarray([tok], jnp.int32))
            tok = int(jnp.argmax(lg[0]))
            gen.append(tok)
        out.append(gen)
    return out


# ---------------------------------------------------------------------------
# Parity vs the dense-KV reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [1, 2])
def test_paged_decode_matches_dense(shards):
    """One batched pool gather per step == dense per-sequence KV decode,
    on both the local pool and a 2-shard CREAM-Shard pool."""
    prompts = _prompts(6)
    reqs = _reqs(prompts)
    if shards > 1:
        if jax.device_count() < shards:
            pytest.skip("needs multiple devices")
        vm = VirtualMemory(row_words=64)
        vm.add_pool("kv", 64, Layout.INTERWRAP, boundary=None,
                    shards=shards)
        eng = Engine(CFG, max_batch=4, max_len=32, vm=vm, seed=0)
    else:
        eng = Engine(CFG, max_batch=4, max_len=32, num_rows=64,
                     row_words=64, seed=0)
    eng.serve(reqs)
    ref = _dense_reference(eng, prompts)
    assert [r.generated for r in reqs] == ref


def test_secded_mode_parity_and_capacity():
    """SECDED pool mode decodes identical tokens with fewer pages."""
    prompts = _prompts(4)
    reqs_c = _reqs(prompts)
    reqs_s = _reqs(prompts)
    eng_c = Engine(CFG, max_batch=4, max_len=32, mode="cream",
                   num_rows=64, row_words=64, seed=0)
    eng_s = Engine(CFG, max_batch=4, max_len=32, mode="secded",
                   num_rows=64, row_words=64, seed=0)
    out_c = eng_c.serve(reqs_c)
    out_s = eng_s.serve(reqs_s)
    assert [r.generated for r in reqs_c] == [r.generated for r in reqs_s]
    assert out_c["device_pages"] > out_s["device_pages"]


def test_one_gather_one_scatter_per_step(monkeypatch):
    """A decode step touches the pool exactly twice: one batched read of
    every block, one batched write of the current blocks."""
    calls = {"read": 0, "write": 0}
    orig_write = PoolState.write

    def counting_write(self, pages, data, **kw):
        calls["write"] += 1
        return orig_write(self, pages, data, **kw)

    eng = Engine(CFG, max_batch=4, max_len=32, num_rows=64, row_words=64)
    for r in _reqs(_prompts(4), max_new=4):
        eng.submit(r)
    eng.poll()                      # admissions + prefill + first step
    orig_gather = eng._gather_pages

    def counting_gather(phys):
        calls["read"] += 1
        return orig_gather(phys)

    eng._gather_pages = counting_gather
    monkeypatch.setattr(PoolState, "write", counting_write)
    eng.poll()                      # a pure decode step
    assert calls == {"read": 1, "write": 1}
    b, L, maxb = eng.max_batch, eng.n_layers, eng.kv.max_blocks
    # and the read really is the whole batch's block tables at once
    rows = np.asarray([s.row if s is not None else -1
                       for s in eng.sched.slots])
    assert eng.kv.gather_phys(rows).shape == (b, L, maxb)


def test_one_dispatch_per_step_sharded(monkeypatch):
    """On a CREAM-Shard pool a decode step is ONE planned device dispatch
    for the gather and ONE for the scatter — the fused path, not a
    per-shard translate/select chain."""
    if jax.device_count() < 2:
        pytest.skip("needs multiple devices")
    from repro.shard import pool as shard_pool

    calls = {"read": 0, "write": 0}
    orig_read = shard_pool._read_planned_jitted
    orig_write = shard_pool._write_planned_jitted

    def counting_read(*a, **kw):
        calls["read"] += 1
        return orig_read(*a, **kw)

    def counting_write(*a, **kw):
        calls["write"] += 1
        return orig_write(*a, **kw)

    vm = VirtualMemory(row_words=64)
    vm.add_pool("kv", 64, Layout.INTERWRAP, boundary=None, shards=2)
    eng = Engine(CFG, max_batch=4, max_len=32, vm=vm, seed=0)
    for r in _reqs(_prompts(4), max_new=4):
        eng.submit(r)
    eng.poll()                      # admissions + prefill + first step
    monkeypatch.setattr(shard_pool, "_read_planned_jitted", counting_read)
    monkeypatch.setattr(shard_pool, "_write_planned_jitted", counting_write)
    eng.poll()                      # a pure decode step
    assert calls == {"read": 1, "write": 1}


# head_dim 12: a token is 48 words, so a 512-word page holds 10 tokens and
# keeps a 32-word tail that is not K/V
CFG_TAIL = dataclasses.replace(CFG, name="serve-test-tail", head_dim=12)
MAX_LEN = 32


@pytest.mark.parametrize("cfg", [CFG, CFG_TAIL], ids=["no_tail", "tail"])
@pytest.mark.parametrize("where", ["zero", "block_end", "block_start",
                                   "middle", "last"])
def test_attend_write_back_matches_numpy(monkeypatch, cfg, where):
    """The pages the model step returns for the scatter are each bound
    slot's current block (``lens // bt`` of every layer) with the step's new
    K and V written at ``lens % bt`` and the page's tail as it was, bit for
    bit; slot 3 is inactive (length 0, every block the scratch page)."""
    eng = Engine(cfg, max_batch=4, max_len=MAX_LEN, num_rows=64,
                 row_words=64, seed=0)
    B, L, maxB, bt = eng.max_batch, eng.n_layers, eng.kv.max_blocks, eng._bt
    kvw, pw = eng.kv.kv_words, eng.kv.page_words
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    assert (pw > kvw) == (cfg is CFG_TAIL)
    n = {"zero": 0, "block_end": bt - 1, "block_start": bt,
         "middle": MAX_LEN // 2 + 1, "last": MAX_LEN - 1}[where]
    lens = np.array([n, MAX_LEN - 1 - n, (n + bt // 2) % MAX_LEN, 0],
                    np.int32)
    rng = np.random.default_rng(n)
    pages = rng.integers(0, 2**32, (B, L, maxB, pw), dtype=np.uint32)
    pages[..., :kvw] = rng.standard_normal(
        (B, L, maxB, kvw)).astype(np.float32).view(np.uint32)
    pages[3] = pages[3, 0, 0]                       # the scratch page
    k_new = rng.standard_normal((L, B, hkv, hd)).astype(np.float32)
    v_new = rng.standard_normal((L, B, hkv, hd)).astype(np.float32)

    def model_step(params, cfg_, state, toks, kv):
        return (jnp.zeros((B, cfg_.vocab_size), jnp.float32), state,
                (jnp.asarray(k_new), jnp.asarray(v_new)))

    monkeypatch.setattr(transformer, "decode_step_paged", model_step)
    _, _, got = jax.jit(eng._attend_fn)(
        eng.params, jnp.asarray(pages.reshape(B * L * maxB, pw)),
        jnp.asarray(lens), jnp.zeros(B, jnp.int32))

    want = pages[np.arange(B)[:, None], np.arange(L), lens[:, None] // bt]
    kv = want[..., :kvw].view(np.float32).reshape(B, L, 2, bt, hkv, hd)
    for b in range(B):
        kv[b, :, 0, lens[b] % bt] = k_new[:, b]
        kv[b, :, 1, lens[b] % bt] = v_new[:, b]
    np.testing.assert_array_equal(np.asarray(got), want.reshape(B * L, pw))


# ---------------------------------------------------------------------------
# Preemption / capacity pressure
# ---------------------------------------------------------------------------


def test_overflow_preemption_is_bit_exact():
    """A pool too small for the working set forces preempt-to-host; the
    token streams must not change."""
    prompts = _prompts(8)
    reqs_big = _reqs(prompts)
    reqs_small = _reqs(prompts)
    Engine(CFG, max_batch=4, max_len=32, num_rows=64, row_words=64,
           seed=0).serve(reqs_big)
    out = Engine(CFG, max_batch=4, max_len=32, num_rows=24, row_words=64,
                 seed=0).serve(reqs_small)
    assert out["preemptions"] > 0
    assert [r.generated for r in reqs_small] == \
        [r.generated for r in reqs_big]


def test_tight_token_budget_resume_does_not_reset():
    """A preempted-then-resumed request carries partial ``generated``; the
    scheduler must measure the *remaining* tokens against the block table
    (not the full max_new), or it would spuriously reset the session and
    decode the tail against a truncated context."""
    prompts = _prompts(8)
    # 12-token prompt + 20 new = 31 <= the 32-token table: zero slack
    ref = _reqs(prompts, max_new=20)
    got = _reqs(prompts, max_new=20)
    Engine(CFG, max_batch=4, max_len=32, num_rows=64, row_words=64,
           seed=0).serve(ref)
    out = Engine(CFG, max_batch=4, max_len=32, num_rows=24, row_words=64,
                 seed=0).serve(got)
    assert out["preemptions"] > 0 and out["restores"] > 0
    assert out["resets"] == 0
    assert [r.generated for r in got] == [r.generated for r in ref]


def test_over_budget_request_fails_fast():
    """prompt + max_new beyond the block table raises at submit, not as a
    mid-serve crash."""
    eng = Engine(CFG, max_batch=2, max_len=32, num_rows=32, row_words=64)
    with pytest.raises(ValueError, match="exceed"):
        eng.submit(ServeRequest("x", _prompts(1)[0], max_new=30))


def _drive(repartition_at=None, new_boundary=0):
    """Serve 8 sessions, optionally repartitioning mid-decode."""
    prompts = _prompts(8)
    reqs = _reqs(prompts, max_new=10)
    eng = Engine(CFG, max_batch=4, max_len=32, num_rows=32, row_words=64,
                 seed=0)
    for r in reqs:
        eng.submit(r)
    mig = MigrationEngine(eng.vm)
    info = None
    k = 0
    while eng.sched.has_work():
        eng.poll()
        k += 1
        if k == repartition_at:
            info = mig.repartition_with_migration("kv", new_boundary)
            eng.refresh_translation()
    return [r.generated for r in reqs], eng, info


def test_midrun_repartition_preempts_and_resumes_bit_exact():
    """The satellite scenario: a mid-decode protection upgrade shrinks the
    NONE pool; mapped extra pages migrate (some to host), the scheduler
    preempts the affected batch-tier sequences, resumes them when frames
    free up, and the decoded tokens are bit-exact vs an unpreempted run."""
    base, _, _ = _drive()
    got, eng, info = _drive(repartition_at=12, new_boundary=0)
    assert info is not None and info["migrated"] > 0
    assert info["to_host"] > 0, "repartition should overflow to host"
    assert eng.sched.restores > 0, "a preempted sequence must resume"
    assert eng.vm.stats.host_reads > 0, "resume pays the page fault"
    assert got == base


def test_paid_tier_lands_on_secded_frames():
    """HRM-style tiers: paid sequences' pages must sit on frames whose
    storage class is SECDED even in cream mode."""
    eng = Engine(CFG, max_batch=2, max_len=32, mode="cream", num_rows=32,
                 secded_rows=16, row_words=64, seed=0)
    reqs = [ServeRequest("paid0", _prompts(1)[0], 4, tier="paid"),
            ServeRequest("batch0", _prompts(1)[0], 4, tier="batch")]
    eng.serve(reqs)
    kv = eng.kv
    for seq, want in (("paid0", {Protection.SECDED}),
                      ("batch0", {Protection.SECDED, Protection.NONE})):
        row = eng.sched.sessions[seq].row
        vpns = kv._table[row][kv._table[row] >= 0]
        assert len(vpns)
        prot = {eng.vm.effective_protection(kv.tenant, int(v))
                for v in vpns}
        assert prot <= want, f"{seq}: {prot}"


# ---------------------------------------------------------------------------
# Scheduled migration overlapped with decode compute
# ---------------------------------------------------------------------------


def _free_pages(vm, pool_name, n):
    """Physical frames the KV allocator is NOT using, oldest first."""
    alloc = vm.allocators[pool_name]
    phys = [p for cls in alloc.free for p in alloc.free[cls]]
    assert len(phys) >= n, "test needs spare frames"
    return np.asarray(phys[:n], np.int32), np.asarray(phys[n:2 * n], np.int32)


@pytest.mark.parametrize("shards", [1, 2])
def test_schedule_migration_overlaps_decode(shards):
    """A migration queued via :meth:`Engine.schedule_migration` runs during
    the next decode step — fused into the attend program's ppermute ring on
    sharded pools, one fused dispatch on local pools — moves the pages
    bit-exactly, and leaves the decoded tokens untouched."""
    if shards > 1 and jax.device_count() < shards:
        pytest.skip("needs multiple devices")
    prompts = _prompts(4)

    def build():
        if shards > 1:
            vm = VirtualMemory(row_words=64)
            vm.add_pool("kv", 64, Layout.INTERWRAP, boundary=None,
                        shards=shards)
            return Engine(CFG, max_batch=4, max_len=32, vm=vm, seed=0)
        return Engine(CFG, max_batch=4, max_len=32, num_rows=64,
                      row_words=64, seed=0)

    # control: same prompts, no migration
    ctl = build()
    ctl_reqs = _reqs(prompts, max_new=6)
    ctl.serve(ctl_reqs)

    eng = build()
    reqs = _reqs(prompts, max_new=6)
    for r in reqs:
        eng.submit(r)
    eng.poll()                                  # admissions + prefill + step
    src, dst = _free_pages(eng.vm, eng.pool_name, 3)
    blob = np.arange(3 * eng.pool.page_words,
                     dtype=np.uint32).reshape(3, -1) | 0xA0000000
    eng.vm.pools[eng.pool_name] = eng.pool.write(src, jnp.asarray(blob))

    ring_calls = {"n": 0}
    orig_ring = eng._attend_ring

    def counting_ring(*a):
        ring_calls["n"] += 1
        return orig_ring(*a)

    eng._attend_ring = counting_ring
    eng.schedule_migration(src, dst)
    eng.poll()                                  # the overlapped step
    assert eng._pending_migration is None
    if shards > 1:
        assert ring_calls["n"] == 1, "sharded pools must take the fused ring"
    else:
        assert ring_calls["n"] == 0
    np.testing.assert_array_equal(np.asarray(eng.pool.read(dst)), blob)
    while eng.sched.has_work():
        eng.poll()
    assert [r.generated for r in reqs] == [r.generated for r in ctl_reqs]


def test_schedule_migration_coalesces_and_validates():
    eng = Engine(CFG, max_batch=2, max_len=32, num_rows=64, row_words=64,
                 seed=0)
    eng.schedule_migration([1, 2], [3, 4])
    eng.schedule_migration([5], [6])
    src, dst = eng._pending_migration
    assert src.tolist() == [1, 2, 5] and dst.tolist() == [3, 4, 6]
    with pytest.raises(ValueError):
        eng.schedule_migration([1, 2], [3])
