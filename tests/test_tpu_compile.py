"""The main-path pool kernels compile for a described TPU v5e, at real widths.

Nothing runs: each test lowers one Pallas kernel for a ``v5e:2x2`` topology
described by the installed TPU compiler and compiles it, so Mosaic refuses
here whatever it would refuse on the chip (illegal block shapes, unsupported
vector layouts, VMEM overuse). Sizes are those ``chip_smoke.py`` serves
``qwen3-0.6b`` at: 2048-word lanes (64 KiB pages), a 12800-row pool, and
the decode step's ``8 x 28 x 64`` page gather.

The topology, shardings and shapes are built in fixtures — never at import —
so only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.layouts import Layout

W = 2048                       # row_words: 8 lanes x 2048 words = 64 KiB pages
ROWS = 12800                   # local pool rows (4 banks x 3200)
CREAM_ROWS = 2048              # the smoke's CREAM region; the rest SECDED
SHARDS = 4
GATHER = 8 * 28 * 64           # max_batch x layers x max_blocks
BENCH_ROWS = 25600             # the chip benchmark's pool
BENCH_SECDED_ROWS = 7168       # of it, the paid tier's SECDED rows
HBM_BYTES = 16 * 2**30         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Described-chip compiles cannot be read back from the persistent
    cache (no chip), so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def native(monkeypatch, no_persistent_cache):
    """Steer the kernel modules off interpret mode (the host is a CPU)."""
    from repro.kernels.hash import kernel as hash_k
    from repro.kernels.migrate import kernel as migrate_k
    from repro.kernels.mixed import kernel as mixed_k
    for mod in (mixed_k, migrate_k, hash_k):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)


def _compile(fn, static, *args):
    """Fresh jit of the kernel's wrapped body (no stale interpret trace)."""
    return jax.jit(fn.__wrapped__, static_argnames=static) \
        .lower(*args).compile()


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES // 2, used


def _pool(sharding, rows=ROWS):
    return jax.ShapeDtypeStruct((rows, 9, W), jnp.uint32, sharding=sharding)


def _ids(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


@pytest.mark.parametrize("boundary", [CREAM_ROWS, 0],
                         ids=["cream", "secded"])
def test_mixed_read_correct_compiles(native, one_chip, boundary):
    from repro.kernels.mixed import kernel
    _check(_compile(kernel.read_correct, ("layout", "num_rows", "boundary"),
                    _pool(one_chip), _ids(GATHER, one_chip),
                    Layout.INTERWRAP, ROWS, boundary))


@pytest.mark.parametrize("boundary", [BENCH_ROWS - BENCH_SECDED_ROWS, 0],
                         ids=["cream", "secded"])
def test_mixed_read_correct_reads_the_pool_in_place(native, one_chip,
                                                    boundary):
    """At the benchmark's pool the gather reads the storage through its
    plane view: no copy or transpose of the pool, next to no temporaries
    (the per-slice kernel's relayout took ~5.2 GB here)."""
    from repro.kernels.mixed import kernel
    compiled = _compile(kernel.read_correct,
                        ("layout", "num_rows", "boundary"),
                        _pool(one_chip, BENCH_ROWS), _ids(GATHER, one_chip),
                        Layout.INTERWRAP, BENCH_ROWS, boundary)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    relayouts = [line for line in text.splitlines()
                 if ("copy(" in line or "transpose(" in line)
                 and str(BENCH_ROWS) in line]
    assert not relayouts, relayouts
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_mixed_read_correct_routed_compiles(native, one_chip):
    """One bank's slice of a 4-bank pool, global ids, traced shard id."""
    from repro.kernels.mixed import kernel
    sid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _check(_compile(kernel.read_correct_routed,
                    ("layout", "num_rows", "boundary", "num_shards"),
                    _pool(one_chip, ROWS // SHARDS), _ids(GATHER, one_chip),
                    Layout.INTERWRAP, ROWS, CREAM_ROWS, SHARDS, sid))


def test_migrate_gather_encode_compiles(native, one_chip):
    from repro.kernels.migrate import kernel
    _check(_compile(kernel.gather_encode, ("num_rows",),
                    _pool(one_chip), _ids(28 * 18, one_chip), ROWS))


def test_hash_lookup_read_compiles(native, one_chip):
    from repro.kernels.hash import kernel
    cap = 16384                            # the smoke's 4 x 4096 values
    keys = jax.ShapeDtypeStruct((cap,), jnp.uint32, sharding=one_chip)
    queries = jax.ShapeDtypeStruct((4160,), jnp.uint32, sharding=one_chip)
    _check(_compile(kernel.lookup_read,
                    ("layout", "num_rows", "boundary", "probe"),
                    _pool(one_chip), keys, _ids(cap, one_chip), queries,
                    Layout.INTERWRAP, ROWS, CREAM_ROWS, 16))


@pytest.fixture(scope="module")
def banks(topo):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(topo.devices), ("banks",),
                axis_types=(AxisType.Auto,))


@pytest.mark.parametrize("path", ["planned", "routed"])
def test_sharded_pool_read_compiles(native, banks, path):
    """The whole sharded read over a 4-chip banks mesh: per-bank kernels
    inside ``shard_map`` (planned streams, or the router-fused kernel under
    a traced read) — the kernels' outputs must type-check as varying."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.shard import pool as sp
    from repro.shard import router
    storage = jax.ShapeDtypeStruct((SHARDS, ROWS // SHARDS, 9, W),
                                   jnp.uint32,
                                   sharding=NamedSharding(banks, P("banks")))
    pool = sp.ShardedPool(storage, CREAM_ROWS // SHARDS,
                          Layout.INTERWRAP, W, banks, True, 0)
    rep = NamedSharding(banks, P())
    if path == "planned":
        spages, _, inv = router.plan_streams(
            np.arange(GATHER, dtype=np.int32) % ROWS, ROWS, SHARDS)
        args = (jax.ShapeDtypeStruct(spages.shape, jnp.int32,
                                     sharding=NamedSharding(banks,
                                                            P("banks"))),
                jax.ShapeDtypeStruct(inv.shape, jnp.int32, sharding=rep))
        fn = sp._read_planned_impl
    else:
        args = (jax.ShapeDtypeStruct((GATHER,), jnp.int32, sharding=rep),)
        fn = sp.read_any
    _check(jax.jit(fn).lower(pool, *args).compile())


def _gathers(hlo: str) -> list[tuple[list[int], int]]:
    """(slice_sizes, operand elements) of every ``gather`` in HLO text. An
    operand is named, so its shape is read from the nearest definition
    above (a fusion's ``parameter``, or the instruction that made it)."""
    import math
    import re
    shapes, out = {}, []
    for line in hlo.splitlines():
        d = re.match(r"\s*(?:ROOT )?(%[\w.-]+) = \w+\[([\d,]*)\]", line)
        if d:
            shapes[d.group(1)] = [int(x) for x in d.group(2).split(",") if x]
        g = re.search(r" gather\((%[\w.-]+),.*slice_sizes=\{([\d,]*)\}",
                      line)
        if g:
            out.append(([int(x) for x in g.group(2).split(",")],
                        math.prod(shapes[g.group(1)])))
    return out


def test_attend_write_back_is_a_row_gather(one_chip, no_persistent_cache,
                                           monkeypatch):
    """The decode model step at the benchmark's page geometry (8 slots,
    64 blocks of 16384-word pages, Qwen3-0.6B widths, 2 of its 28 layers)
    picks each slot's current block as whole page rows: no gather of single
    elements over the padded K/V, the form that cost ~82 ms a step."""
    import dataclasses

    from repro.configs import get_config
    from repro.serve import Engine
    from repro.serve import engine as engine_mod
    real_build = engine_mod.build_model

    def shapes_only(cfg):
        model = real_build(cfg)
        return dataclasses.replace(model, init=lambda key: jax.eval_shape(
            model.init, key))

    monkeypatch.setattr(engine_mod, "build_model", shapes_only)
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=2,
                              dtype="float32")
    eng = Engine(cfg, max_batch=8, max_len=512, num_rows=64, row_words=W)
    B, L, maxB = eng.max_batch, eng.n_layers, eng.kv.max_blocks
    assert (maxB, eng.kv.page_words) == (64, 8 * W)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), eng.params)
    vec = sds((B,), jnp.int32)
    text = jax.jit(eng._attend_fn).lower(
        params, sds((B * L * maxB, 8 * W), jnp.uint32), vec, vec) \
        .compile().as_text()
    gathers = _gathers(text)
    assert gathers, "the write-back's row gather is missing"
    scalar = [g for g in gathers if set(g[0]) == {1} and g[1] > 10**6]
    assert not scalar, scalar
