#!/usr/bin/env python3
"""Chip smoke: CREAM-Serve end to end on a TPU at qwen3-0.6b's published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # KV pool sharded over a 4-chip banks mesh

One process, no children. It refuses to run unless JAX's first device is a
TPU, and every check that fails ends the run with a non-zero exit before the
result line. Weights are random (from ``--seed``); every width is the
published one, with the KV kept as float32 (the paged KV holds 4-byte words).

Default phase (one chip), through ``repro.serve.Engine`` / ``ServeRequest``:

  * ``mode="cream"`` with a SECDED region (paid tier) beside an InterWrap
    CREAM region whose reclaimed extra pages batch sessions spill into.
    After the first decode step one bit is flipped in a paid session's
    SECDED KV page, where it stays for the rest of the run; the Pallas
    gather must correct it, and every gathered page must equal the
    ``kernels/mixed/ref.py`` oracle bit for bit. Then one protection
    upgrade (``MigrationEngine.repartition_with_migration``) relocates the
    mapped extra pages it dooms through the migrate kernel, mid-serve;
  * ``mode="secded"`` serves the same requests; its tokens must equal the
    cream run's (no token lost to the flip or the upgrade);
  * every served token is checked against the dense path (``model.prefill``
    plus teacher-forced ``model.decode_step`` over the same params);
  * an ``ObjCache`` set/get of a few thousand values, checked against a
    dict, drives the fused hash kernel.

``--chips 4`` runs only the sharded path and what it is compared with: the
same requests on a one-chip local pool, then on a pool sharded over a
4-chip ``banks`` mesh (planned bank-aligned streams, the router-fused
``read_correct_routed`` kernel under a traced read, and one ``ppermute``
ring migration via ``Engine.schedule_migration``). The tokens must match.

Printed figures are smoke figures, not benchmark results. The last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Pool and traffic sizes. Defaults: qwen3-0.6b at full width on one
    v5e — 64 KiB pages (8 tokens of one layer's f32 K+V), 12800 rows
    (0.94 GB of pool), a decode step gathering 8 x 28 x 64 pages."""
    row_words: int = 2048
    num_rows: int = 12800
    cream_rows: int = 2048       # CREAM region in cream mode; the rest SECDED
    upgrade_to: int = 1792       # mid-serve protection upgrade: new boundary
    max_batch: int = 8
    max_len: int = 512
    prompt_len: int = 128
    max_new: int = 16
    sessions: int = 8            # each serves `turns` requests (parks between)
    turns: int = 2
    paid: int = 2                # sessions on the SECDED paid tier
    obj_values: int = 4096
    obj_words: int = 64


#: Largest gap allowed between the dense path's best logit and its logit for
#: the served token. TPU matmuls on float32 round operands to bfloat16
#: (8 significant bits, relative 2^-9 ~ 2e-3) unless asked for more, so two
#: programs that sum the same terms in a different order can swap logits
#: that lie closer than that; the bound is relative to the row's max |logit|.
LOGIT_TOL = 1e-2


def model_config(name: str = "qwen3-0.6b"):
    from repro.configs import get_config
    return dataclasses.replace(get_config(name), dtype="float32")


def make_requests(cfg, g: Geometry, seed: int):
    import numpy as np

    from repro.serve import ServeRequest
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, g.prompt_len).astype(np.int32)
               for _ in range(g.sessions)]
    return prompts, [ServeRequest(f"s{s}", prompts[s], g.max_new,
                                  tier="paid" if s < g.paid else "batch")
                     for _ in range(g.turns) for s in range(g.sessions)]


def tokens_by_session(reqs) -> dict[str, list[int]]:
    out: dict[str, list[int]] = {}
    for r in reqs:
        out.setdefault(r.seq_id, []).extend(r.generated)
    return out


def drive(eng, reqs, hook=None) -> dict:
    """Serve ``reqs`` to completion through ``Engine.poll``; ``hook(eng,
    polls)`` runs after every poll. Returns the poll count, the seconds of
    the first poll (set-up: it compiles prefill, gather, attend and
    scatter) and of the whole loop, hooks excluded."""
    for r in reqs:
        eng.submit(r)
    polls, first, total = 0, 0.0, 0.0
    while eng.sched.has_work():
        t = time.perf_counter()
        eng.poll()
        dt = time.perf_counter() - t
        first, total, polls = first or dt, total + dt, polls + 1
        if hook is not None:
            hook(eng, polls)
    return {"polls": polls, "setup_s": first, "serve_s": total}


def mapped_extras(eng) -> list[int]:
    pool = eng.pool
    return sorted(p for p in eng.vm.allocators[eng.pool_name].owner
                  if p >= pool.num_rows)


def batch_phys(eng):
    """The page ids of the live batch's block tables (the decode gather)."""
    import numpy as np
    rows = np.asarray([s.row if s is not None else -1
                       for s in eng.sched.slots])
    return eng.kv.gather_phys(rows).reshape(-1)


def gather_matches_oracle(eng, chunk: int = 64) -> int:
    """The engine's decode gather == the jnp oracle on the same storage,
    bit for bit, for every distinct page of the live batch's block tables.
    The oracle pairs words through a trailing axis of 2, which a TPU pads
    to 128, so it runs ``chunk`` pages at a time. Returns pages compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.mixed import ref
    phys = batch_phys(eng)
    pool = eng.pool
    got = eng._gather_pages(phys)
    pages, first = np.unique(phys, return_index=True)
    oracle = jax.jit(ref.read_correct, static_argnums=(2, 3, 4))
    for i in range(0, len(pages), chunk):
        ids = np.resize(pages[i:i + chunk], chunk)       # one compiled shape
        want = oracle(pool.storage, jnp.asarray(ids), pool.layout,
                      pool.num_rows, pool.boundary)
        mine = got[jnp.asarray(np.resize(first[i:i + chunk], chunk))]
        check(bool(jnp.array_equal(mine, want)),
              "decode gather differs from the kernels/mixed/ref.py oracle")
    return len(pages)


def flip_corrected(eng, seq_id: str) -> int:
    """Flip one data bit of a SECDED page holding ``seq_id``'s KV; the
    engine's gather kernel must return the page as it was. Returns the
    page id. The flip stays in storage for the rest of the run."""
    import jax.numpy as jnp
    import numpy as np
    pool = eng.pool
    row = eng.sched.sessions[seq_id].row
    phys = eng.kv.gather_phys(np.asarray([row]))[0, 0]     # layer 0 blocks
    page = int(next(p for p in phys if pool.boundary <= p < pool.num_rows))
    ids = np.asarray([page], np.int32)
    before = np.asarray(eng._gather_pages(ids))
    lane, word, bit = 3, 17 % pool.row_words, 9
    storage = pool.storage.at[page, lane, word].set(
        pool.storage[page, lane, word] ^ jnp.uint32(1 << bit))
    eng.vm.pools[eng.pool_name] = dataclasses.replace(pool, storage=storage)
    raw = np.asarray(storage[page, :lane + 1]).reshape(-1)
    check(int(np.bitwise_count(raw ^ before[0, :raw.size]).sum()) == 1,
          "the flip did not land in storage")
    check(np.array_equal(np.asarray(eng._gather_pages(ids)), before),
          "the gather kernel did not correct the injected SECDED flip")
    return page


def dense_agreement(eng, prompts, served: dict[str, list[int]]) -> dict:
    """Teacher-forced dense reference: prefill each prompt, then feed the
    served tokens through ``model.decode_step``. Every served token must be
    the dense argmax up to :data:`LOGIT_TOL`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model, params = eng.model, eng.params
    pre = jax.jit(lambda p, t: model.prefill(p, t, eng.max_len))
    step = jax.jit(model.decode_step)
    exact = total = 0
    worst = 0.0
    for s, prompt in enumerate(prompts):
        toks = served[f"s{s}"]
        logits, state = pre(params, jnp.asarray(prompt[None, :]))
        rows = [logits[0, -1]]
        for t in toks[:-1]:
            lg, state = step(params, state, jnp.asarray([t], jnp.int32))
            rows.append(lg[0])
        lg = np.asarray(jnp.stack(rows), np.float64)
        best = lg.max(axis=1)
        mine = lg[np.arange(len(toks)), np.asarray(toks)]
        gap = (best - mine) / np.abs(lg).max(axis=1)
        exact += int((gap == 0).sum())
        total += len(toks)
        worst = max(worst, float(gap.max()))
    check(worst <= LOGIT_TOL,
          f"served token trails the dense argmax by {worst:.3g} "
          f"(relative; bound {LOGIT_TOL})")
    return {"tokens": total, "dense_argmax_equal": exact,
            "max_rel_logit_gap": worst}


def serve_cream(cfg, g: Geometry, seed: int, checks: dict):
    """The cream run: flip + oracle checks after the first poll, a
    protection upgrade once reclaimed pages are mapped."""
    from repro.serve import Engine
    from repro.vm.migration import MigrationEngine
    prompts, reqs = make_requests(cfg, g, seed)
    eng = Engine(cfg, max_batch=g.max_batch, max_len=g.max_len, mode="cream",
                 num_rows=g.num_rows, row_words=g.row_words,
                 secded_rows=g.num_rows - g.cream_rows, seed=seed)
    mig = MigrationEngine(eng.vm)
    state = {"flip": None, "upgrade": None}

    def hook(eng, polls):
        if polls == 1:
            state["flip"] = flip_corrected(eng, "s0")
            checks["oracle_pages"] = gather_matches_oracle(eng)
            checks["regions_at_first_step"] = regions(eng)
        doomed = [p for p in mapped_extras(eng)
                  if p - eng.pool.num_rows >= g.upgrade_to // 8]
        if state["upgrade"] is None and doomed:
            info = mig.repartition_with_migration(eng.pool_name, g.upgrade_to)
            dropped = eng.refresh_translation()
            check(info["migrated"] == len(doomed) > 0,
                  f"upgrade migrated {info['migrated']} of {len(doomed)} "
                  "mapped extra pages")
            check(mig.stats.kernel_batches > 0,
                  "the upgrade did not run the migrate kernel")
            state["upgrade"] = {"at_poll": polls, "migrated": info["migrated"],
                                "to_host": info["to_host"],
                                "preempted_slots": len(dropped)}
            checks["oracle_pages_after_upgrade"] = gather_matches_oracle(eng)

    run = drive(eng, reqs, hook)
    check(state["flip"] is not None, "no SECDED page to flip")
    check(state["upgrade"] is not None, "reclaimed pages were never mapped")
    checks["upgrade"] = state["upgrade"]
    return eng, prompts, reqs, run


def regions(eng) -> dict[str, int]:
    """Mapped pages by region: SECDED rows, CREAM rows, reclaimed extras."""
    pool = eng.pool
    owned = list(eng.vm.allocators[eng.pool_name].owner)
    sec = sum(pool.boundary <= p < pool.num_rows for p in owned)
    extra = sum(p >= pool.num_rows for p in owned)
    return {"secded": sec, "cream": len(owned) - sec - extra, "extra": extra}


def objcache_phase(g: Geometry, seed: int) -> dict:
    """A few thousand set/gets through the fused hash kernel vs a dict."""
    import numpy as np

    from repro.core.layouts import Layout
    from repro.core.protection import Protection
    from repro.objcache.cache import ObjCache
    from repro.vm.address_space import VirtualMemory
    vm = VirtualMemory(row_words=g.row_words)
    vm.add_pool("obj", 64, Layout.INTERWRAP, boundary=32)
    w = g.obj_words                     # value-sized slab chunks, not pages
    cache = ObjCache(vm, "obj", index_capacity=4 * g.obj_values,
                     max_value_words=w, chunk_words=(w // 4, w // 2, w))
    rng = np.random.default_rng(seed)
    keys = rng.permutation(1 << 20)[:g.obj_values].astype(np.int64)
    vals = rng.integers(0, 2**32, (g.obj_values, g.obj_words), np.uint32)
    lens = rng.integers(1, g.obj_words + 1, g.obj_values)
    half = g.obj_values // 2
    ok = cache.set_many(keys[:half], vals[:half], lens[:half])
    ok2 = cache.set_many(keys[half:], vals[half:], lens[half:],
                         reliability=Protection.SECDED)
    check(bool(ok.all() and ok2.all()), "ObjCache refused a value")
    truth = {int(k): v[:n] for k, v, n in zip(keys, vals, lens)}
    probe = np.concatenate([keys, keys[:64] + (1 << 21)])   # + 64 misses
    got, glens, found = cache.get_many(probe)
    for k, v, n, f in zip(probe, got, glens, found):
        want = truth.get(int(k))
        check(bool(f) == (want is not None), f"key {k}: found={f}")
        if want is not None:
            check(int(n) == len(want) and np.array_equal(v[:n], want),
                  f"key {k}: value differs from the dict")
    return {"values": g.obj_values, "gets": int(probe.size),
            "hits": int(found.sum())}


def lowered_gather(eng) -> str:
    import jax.numpy as jnp
    pool = eng.pool
    pages = jnp.zeros((eng.max_batch * eng.n_layers * eng.kv.max_blocks,),
                      jnp.int32)
    return eng._mixed_read.lower(pool.storage, pages, layout=pool.layout,
                                 num_rows=pool.num_rows,
                                 boundary=pool.boundary).as_text()


def summary(eng, run: dict) -> str:
    return (f"{run['polls']} polls, {eng.steps} decode steps, "
            f"{eng.vm.used_device_pages('kv')} pages resident of "
            f"{eng.vm.device_capacity_pages('kv')}, set-up (first poll, "
            f"compiles) {run['setup_s']:.3f} s, serve loop "
            f"{run['serve_s']:.3f} s (smoke figures, not a benchmark)")


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(cfg, g: Geometry, seed: int) -> None:
    from repro.kernels.common import use_interpret
    from repro.serve import Engine
    check(not use_interpret(), "Pallas kernels would run interpreted")
    say("smoke: DAEC tier off and metrics off, so the decode gather is the "
        "fused Pallas mixed-read kernel (the engine's jnp path serves those)")
    checks: dict = {}
    t0 = time.perf_counter()
    eng, prompts, reqs, run = serve_cream(cfg, g, seed, checks)
    text = lowered_gather(eng)
    check("tpu_custom_call" in text, "the decode gather holds no Pallas call")
    cream_tokens = tokens_by_session(reqs)
    say(f"smoke cream: {summary(eng, run)}; first-step regions "
        f"{checks['regions_at_first_step']}, flipped SECDED page corrected, "
        f"{checks['oracle_pages']} distinct gathered pages == oracle, "
        f"upgrade {checks['upgrade']}")
    agree = dense_agreement(eng, prompts, cream_tokens)
    say(f"smoke dense check: {agree}")
    del eng

    _, reqs_s = make_requests(cfg, g, seed)
    eng_s = Engine(cfg, max_batch=g.max_batch, max_len=g.max_len,
                   mode="secded", num_rows=g.num_rows,
                   row_words=g.row_words, seed=seed)
    run = drive(eng_s, reqs_s)
    check(tokens_by_session(reqs_s) == cream_tokens,
          "secded-mode tokens differ from the cream run")
    say(f"smoke secded: {summary(eng_s, run)}; tokens == cream run")
    say(f"smoke oracle after upgrade: {checks['oracle_pages_after_upgrade']} "
        "distinct gathered pages == oracle")
    del eng_s
    say(f"smoke objcache: {objcache_phase(g, seed)} == dict")
    say(f"smoke setup+run seconds {time.perf_counter() - t0:.3f}, "
        f"peak device bytes {peak_bytes()} (smoke figures)")


def four_chips(cfg, g: Geometry, seed: int) -> None:
    """Sharded KV pool over a 4-chip banks mesh vs a one-chip local pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.layouts import Layout
    from repro.serve import Engine
    from repro.vm.address_space import VirtualMemory
    _, reqs = make_requests(cfg, g, seed)
    local = Engine(cfg, max_batch=g.max_batch, max_len=g.max_len,
                   mode="cream", num_rows=g.num_rows, row_words=g.row_words,
                   secded_rows=g.num_rows - g.cream_rows, seed=seed)
    drive(local, reqs)
    want = tokens_by_session(reqs)
    del local

    vm = VirtualMemory(row_words=g.row_words)
    vm.add_pool("kv", g.num_rows, Layout.INTERWRAP, boundary=g.cream_rows,
                shards=4)
    eng = Engine(cfg, max_batch=g.max_batch, max_len=g.max_len, vm=vm,
                 seed=seed)
    _, reqs4 = make_requests(cfg, g, seed)
    ring = {}

    def hook(eng, polls):
        if polls == 2:          # the step after scheduling ran the ring
            check(eng._pending_migration is None,
                  "the ring migration never ran")
            check(bool(np.array_equal(np.asarray(eng.pool.read(ring["dst"])),
                                      ring["blob"])),
                  "ring-migrated pages differ")
            ring["checked"] = True
        if polls != 1:
            return
        pool = eng.pool
        phys = jnp.asarray(batch_phys(eng))
        planned = pool.read(np.asarray(phys))
        routed = jax.jit(lambda p, ids: p.read(ids))(pool, phys)
        check(bool(jnp.array_equal(planned, routed)),
              "routed read differs from the planned streams")
        # free frames the KV allocator hands out last: the sessions' growth
        # must not overwrite them before the check
        alloc = vm.allocators["kv"]
        free = [p for cls in alloc.free for p in alloc.free[cls]]
        src = np.asarray(free[-16:-8], np.int32)
        dst = np.asarray(free[-8:], np.int32)
        blob = (np.arange(8 * pool.page_words, dtype=np.uint32)
                .reshape(8, -1) | np.uint32(0xA0000000))
        eng.vm.pools["kv"] = pool.write(src, blob)
        eng.schedule_migration(src, dst)
        ring.update(dst=dst, blob=blob, pages=int(phys.size))

    run = drive(eng, reqs4, hook)
    check(ring.get("checked", False), "the ring migration was not checked")
    got = tokens_by_session(reqs4)
    differ = sorted(k for k in want if got.get(k) != want[k])
    check(not differ, f"sharded-pool tokens differ from the one-chip run "
          f"for sessions {differ}")
    say(f"smoke 4-chip: {summary(eng, run)}; "
        f"{sum(map(len, got.values()))} tokens == one-chip local pool, "
        f"routed read == planned streams over {ring['pages']} pages, "
        "8 pages moved by the ppermute ring (smoke figures)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    say(f"smoke: compile cache at {enable_compile_cache()}")
    cfg = model_config()
    g = Geometry()
    try:
        if args.chips == 4:
            four_chips(cfg, g, args.seed)
        else:
            one_chip(cfg, g, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
