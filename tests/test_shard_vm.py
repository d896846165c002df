"""The VM stack runs unchanged on a sharded pool (PoolLike acceptance).

`vm/address_space.py`, `vm/migration.py`, `vm/policy.py`,
`objcache/cache.py` and `serve/kv_cache.py` were written against the
`PoolLike` surface; these tests run their existing flows with the backing
pool sharded over a `banks` mesh and assert nothing observable changes:
allocation, data plane, zero-loss repartition+migration, the object cache,
and sequence parking.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.layouts import Layout  # noqa: E402
from repro.core.protection import Protection  # noqa: E402
from repro.shard import ShardedPool  # noqa: E402
from repro.vm import MigrationEngine, VirtualMemory, VMPolicy  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4+ devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8; the repo conftest sets it)")

ROW_WORDS = 32


def _vm(shards=4, rows=128, boundary=64, layout=Layout.INTERWRAP):
    vm = VirtualMemory(row_words=ROW_WORDS)
    state = vm.add_pool("main", rows, layout, boundary=boundary,
                        shards=shards)
    assert isinstance(state, ShardedPool)
    return vm


def test_vm_alloc_write_read_free_on_sharded_pool():
    vm = _vm()
    rng = np.random.default_rng(0)
    t = vm.create_tenant("t", default_reliability=Protection.NONE)
    vpns = vm.alloc("t", 24)
    blob = rng.integers(0, 2**32, (24, vm.page_words), dtype=np.uint32)
    vm.write("t", vpns, blob)
    np.testing.assert_array_equal(np.asarray(vm.read("t", vpns)), blob)
    # frames really live on the sharded pool
    assert all(t.entries[v].pool == "main" for v in vpns)
    vm.free("t", vpns)
    assert vm.used_device_pages() == 0


def test_vm_swap_roundtrip_on_sharded_pool():
    vm = _vm()
    rng = np.random.default_rng(1)
    vm.create_tenant("t")
    vpns = vm.alloc("t", 8)
    blob = rng.integers(0, 2**32, (8, vm.page_words), dtype=np.uint32)
    vm.write("t", vpns, blob)
    assert vm.swap_out("t", vpns) == 8
    assert vm.residency("t", vpns) == "host"
    np.testing.assert_array_equal(np.asarray(vm.read("t", vpns)), blob)
    assert vm.swap_in("t", vpns) == 8
    assert vm.residency("t", vpns) == "device"
    np.testing.assert_array_equal(np.asarray(vm.read("t", vpns)), blob)


def test_repartition_with_migration_zero_loss_on_sharded_pool():
    vm = _vm(shards=4, rows=128, boundary=128)
    rng = np.random.default_rng(2)
    engine = MigrationEngine(vm)
    vm.create_tenant("bulk", default_reliability=Protection.NONE)
    state = vm.pools["main"]
    # map every page (incl. all extras), then upgrade protection fully:
    # every extra page is doomed and must be relocated, not dropped
    vpns = vm.alloc("bulk", state.num_pages)
    blob = rng.integers(0, 2**32, (len(vpns), vm.page_words), dtype=np.uint32)
    vm.write("bulk", vpns, blob)
    info = engine.repartition_with_migration("main", 0)
    assert info["migrated"] == state.num_extra_pages
    assert vm.pools["main"].boundary == 0
    np.testing.assert_array_equal(np.asarray(vm.read("bulk", vpns)), blob)

    # boundary steps must respect the shard lockstep granularity
    with pytest.raises(ValueError):
        engine.repartition_with_migration("main", 8)   # < 4 shards * 8 rows


def test_policy_scrub_and_adapt_on_sharded_pool():
    vm = _vm(shards=4, rows=128, boundary=128)
    policy = VMPolicy(vm)
    stats = policy.scrub_all()
    assert stats["main"].error_rate == 0.0
    # force an upgrade recommendation by recording a hot error census
    from repro.core.scrubber import ScrubStats
    for _ in range(4):
        policy.monitor.record("main", ScrubStats(
            beats_checked=1000, corrected_data=50))
    infos = policy.adapt()
    assert infos and vm.pools["main"].boundary == 0


def test_objcache_on_sharded_pool():
    from repro.objcache.cache import ObjCache
    vm = _vm(shards=4, rows=128, boundary=128)
    cache = ObjCache(vm, "main", index_capacity=256, max_value_words=48)
    rng = np.random.default_rng(3)
    keys = np.arange(40)
    vals = rng.integers(0, 2**32, (40, 48), dtype=np.uint32)
    stored = cache.set_many(keys, vals)
    assert stored.all()
    got, lens, found = cache.get_many(keys)
    assert found.all()
    np.testing.assert_array_equal(got, vals)
    assert cache.delete_many(keys[:10]).all()
    _, _, found = cache.get_many(keys[:10])
    assert not found.any()


def test_sequence_cache_on_sharded_pool():
    from repro.serve.kv_cache import SequenceCache
    vm = VirtualMemory(row_words=ROW_WORDS)
    vm.add_pool(SequenceCache.POOL, 64, Layout.INTERWRAP, shards=4)
    cache = SequenceCache(num_rows=64, vm=vm)
    rng = np.random.default_rng(4)
    blobs = {f"s{i}": rng.integers(0, 256, 1000, dtype=np.uint8)
             for i in range(6)}
    for sid, blob in blobs.items():
        cache.park(sid, blob)
    out = cache.resume_many(blobs)
    for sid, blob in blobs.items():
        np.testing.assert_array_equal(out[sid], blob)
    assert cache.stats.device_hits == 6


@pytest.mark.parametrize("verb", ["read", "write"])
def test_planned_access_on_banks_mesh(verb):
    """The planned (concrete-id) verbs on a ``make_banks_mesh`` pool.

    ``jax.make_mesh`` defaults to Explicit axis types, under which the
    planned path's permutation gather/scatter raised ``ShardingTypeError``;
    the banks mesh is built with Auto axes. Each case runs one planned verb
    and checks it through the traced (fused) path of the other verb.
    """
    from jax.sharding import AxisType

    from repro.launch.mesh import make_banks_mesh
    from repro.shard.pool import make_sharded_pool
    mesh = make_banks_mesh(4)
    assert mesh.axis_types == (AxisType.Auto,)
    pool = make_sharded_pool(128, Layout.INTERWRAP, 64, num_shards=4,
                             row_words=ROW_WORDS, mesh=mesh)
    pages = np.array([0, 5, 63, 64, 99, 127, 128, 135], np.int32)
    blob = np.random.default_rng(5).integers(
        0, 2**32, (len(pages), pool.page_words), dtype=np.uint32)
    if verb == "write":
        pool = pool.write(pages, blob)
        got = jax.jit(lambda p, ids: p.read(ids))(pool, jnp.asarray(pages))
    else:
        pool = jax.jit(lambda p, ids, d: p.write(ids, d))(
            pool, jnp.asarray(pages), jnp.asarray(blob))
        got = pool.read(pages)
    np.testing.assert_array_equal(np.asarray(got), blob)
