"""CREAM-Shard — the CREAM pool partitioned across a ``banks`` mesh axis.

The paper's second headline claim is that CREAM *increases bank-level
parallelism*: rank subsetting (§4.1.2) splits the DIMM into independently
addressable subsets, and Figs. 9–11 measure the resulting concurrency win.
This module is that mechanism on the real data plane: the pool's rows are
striped round-robin over ``S`` devices of a 1-D ``banks`` mesh
(:func:`repro.launch.mesh.make_banks_mesh`), every shard holds an
identically-shaped mini CREAM pool ``(R_local, 9, W)`` with the same
boundary register, and the whole mixed-pool access engine of
:mod:`repro.core.pool` — one ``page_coords`` translation, one
gather/scatter, masked batched codecs — runs unchanged *inside each shard*
under ``shard_map``. On TPU the per-shard read is the fused Pallas mixed
kernel; on CPU it is the vectorised engine (the kernel's oracle).

Every access is ONE device dispatch, in one of two shapes by id locality:

  * **Fused traced dispatch** — :func:`read_any` / :func:`write_any`,
    arbitrary (possibly traced) global page-id vectors. The router's
    global-id -> (shard, local) translation is *fused into the access
    itself*: reads dispatch the router-aware mixed kernel
    (:func:`repro.kernels.mixed.ops.read_correct_routed`, whose
    scalar-prefetch index map composes routing with the layout
    translation), each shard zeroes the rows it does not own, and a single
    ``psum`` over ``banks`` assembles the replicated batch. Writes compute
    ownership in-body from ``axis_index`` and let the engine's ``valid``
    mask drop foreign pages — no routed operands, no stacked outputs, no
    owner-select chain.
  * **Planned bank-aligned dispatch** — the concrete-id hot path behind
    :meth:`ShardedPool.read` / :meth:`ShardedPool.write`. A host-side
    numpy pass (:func:`repro.shard.router.plan_streams`) regroups the
    batch into ``S`` padded per-bank streams plus one inverse permutation;
    the single jitted program then does a per-bank gather of ~``n/S``
    pages and the device-side permute back to batch order. Per-bank work
    *shrinks* with ``S`` — the measured Figs. 9–11 concurrency story
    (``benchmarks/bench_shard.py``). :func:`read_streams` /
    :func:`write_streams` expose the aligned ``(S, n)`` form directly for
    callers that already hold per-bank streams.

:func:`migrate_pages` relocates pages across shard boundaries as an
explicit ``ppermute`` ring exchange: each shard reads its owned source
pages, the batch circulates around the ring, and every shard lands the
pages addressed to it with a masked code-maintaining write.

:func:`repartition` moves every shard's boundary in lockstep (one
``shard_map`` over the local repartition, which re-encodes in place), so
the global page-id convention — and therefore every owner's bookkeeping —
is preserved exactly as for the local pool.

:class:`ShardedPool` implements :class:`repro.core.pool.PoolLike`; the VM
(:mod:`repro.vm`), object cache (:mod:`repro.objcache`) and serving tier
(:mod:`repro.serve`) run on it unchanged.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import pool as pool_lib
from repro.core.layouts import (GROUP_ROWS, LANES, Layout, extra_page_count)
from repro.core.pool import PoolState
from repro.obs import memprof as obs_memprof
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.shard import router


def _note_dispatch(op: str, pages: int) -> None:
    """Count one routed host-side dispatch through the shard data plane."""
    if not obs_metrics.enabled():
        return
    obs_metrics.counter(
        obs_metrics.NAME_SHARD_DISPATCH,
        "routed dispatches through the sharded data plane",
        labels=("op",)).labels(op=op).inc()


def _memprof_routed(state: "ShardedPool", op: str, pages,
                    stream: str = "main") -> None:
    """Feed one routed dispatch to CREAM-Lens, split per shard.

    Mirrors :func:`repro.shard.router.route` in numpy and records each
    shard's local id set against the shard's *local* geometry (its own
    module: ``rows_local`` rows, ``boundary_local``), stream ``bank<s>``
    — so replay models ``S`` independent BankArrays, exactly the
    rank-subset hardware the sharding claims to be.
    """
    if not obs_memprof.enabled() or isinstance(pages, jax.core.Tracer) \
            or isinstance(state.storage, jax.core.Tracer):
        return
    p = np.asarray(pages, dtype=np.int64).reshape(-1)
    S = state.num_shards
    is_extra = p >= state.num_rows
    e = p - state.num_rows
    shard = np.where(is_extra, e % S, p % S)
    local = np.where(is_extra, state.rows_local + e // S, p // S)
    prefix = "" if stream == "main" else f"{stream}/"
    for s in range(S):
        loc = local[shard == s]
        if loc.size == 0:
            continue
        obs_memprof.record(
            op, loc, layout=state.layout,
            num_rows=state.rows_local,
            boundary=state.boundary_local,
            row_words=state.row_words, stream=f"{prefix}bank{s}")


@jax.tree_util.register_dataclass
@dataclass
class ShardedPool:
    """Functional sharded pool state. ``storage`` is the only traced leaf.

    ``storage`` is ``(S, R_local, 9, W)`` uint32, laid out over the mesh's
    ``banks`` axis (leading dim). All other fields are static pytree
    metadata, so each (geometry, mesh) compiles once — exactly like the
    local pool's (boundary, layout, row_words) treatment.
    """
    storage: jax.Array                  # (S, R_local, 9, W) uint32
    boundary_local: int = dataclasses.field(metadata=dict(static=True))
    layout: Layout = dataclasses.field(metadata=dict(static=True))
    row_words: int = dataclasses.field(metadata=dict(static=True))
    mesh: jax.sharding.Mesh = dataclasses.field(metadata=dict(static=True))
    use_kernel: bool | None = dataclasses.field(
        default=None, metadata=dict(static=True))
    #: Per-shard DAEC-tier depth. Global DAEC rows stripe round-robin like
    #: everything else, so the tier is the top ``daec_rows_local`` rows of
    #: EVERY shard and global ``daec_rows = S * daec_rows_local`` — the
    #: tier boundary needs no per-shard adjustment.
    daec_rows_local: int = dataclasses.field(
        default=0, metadata=dict(static=True))

    # -- geometry (global page-id convention, same as PoolState) ------------
    @property
    def num_shards(self) -> int:
        return self.storage.shape[0]

    @property
    def rows_local(self) -> int:
        return self.storage.shape[1]

    @property
    def num_rows(self) -> int:
        return self.num_shards * self.rows_local

    @property
    def boundary(self) -> int:
        return self.num_shards * self.boundary_local

    @property
    def boundary_step(self) -> int:
        """Boundary moves in lockstep across shards: S * GROUP_ROWS rows."""
        return self.num_shards * GROUP_ROWS

    @property
    def daec_rows(self) -> int:
        return self.num_shards * self.daec_rows_local

    @property
    def daec_start(self) -> int:
        """First global row of the SEC-DAEC tier (= num_rows - daec_rows)."""
        return self.num_rows - self.daec_rows

    @property
    def extra_pages_local(self) -> int:
        return extra_page_count(self.layout, self.boundary_local,
                                self.row_words)

    @property
    def num_extra_pages(self) -> int:
        return self.num_shards * self.extra_pages_local

    @property
    def num_pages(self) -> int:
        return self.num_rows + self.num_extra_pages

    @property
    def page_words(self) -> int:
        return 8 * self.row_words

    @property
    def page_bytes(self) -> int:
        return 4 * self.page_words

    @property
    def raw_bytes(self) -> int:
        return self.storage.size * 4

    @property
    def effective_bytes(self) -> int:
        return self.num_pages * self.page_bytes

    def capacity_gain(self) -> float:
        return self.num_extra_pages / self.num_rows

    # -- PoolLike surface (unified access API) ------------------------------
    def _traced(self, *operands) -> bool:
        return any(isinstance(x, jax.core.Tracer)
                   for x in (self.storage, *operands))

    def read(self, pages, *, status=False):
        """Batch read for arbitrary global page ids — ONE device dispatch.

        Traced ids compose into the enclosing trace via the fused
        router-in-kernel path (:func:`read_any`). Concrete ids take the
        planned bank-aligned path: host-side stream planning, then one
        jitted program whose per-bank gather touches only ~``n/S`` pages.
        """
        if self._traced(pages):
            return read_any_status(self, pages) if status \
                else read_any(self, pages)
        arr = pool_lib._as_page_array(self, pages)
        op = "read_status" if status else "read"
        _note_dispatch(op, arr.shape[0])
        _memprof_routed(self, "gather", arr)
        spages, _, inv = router.plan_streams(arr, self.num_rows,
                                             self.num_shards)
        fn = _read_planned_status_jitted if status else _read_planned_jitted
        with obs_tracing.span("shard.fused.dispatch", op=op,
                              pages=arr.shape[0], shards=self.num_shards):
            return fn(self, jnp.asarray(spages), jnp.asarray(inv, jnp.int32))

    def write(self, pages, data: jax.Array, *, valid=None) -> "ShardedPool":
        """Code-maintaining batch write — ONE device dispatch.

        ``valid`` optionally drops masked entries. Traced operands use the
        fused in-body-ownership path (:func:`write_any`); concrete ids use
        the planned bank-aligned path (pads and masked entries share the
        engine's ``valid`` drop). The concrete path donates this pool's
        storage — drop the old state immediately.
        """
        if self._traced(pages, data, valid):
            return write_any(self, pages, data, valid=valid)
        arr = pool_lib._as_page_array(self, pages)
        n = arr.shape[0]
        data = jnp.asarray(data).astype(jnp.uint32).reshape(n, -1)
        if data.shape[1] != self.page_words:
            raise ValueError(f"page data must be {self.page_words} words")
        _note_dispatch("write", n)
        _memprof_routed(self, "scatter", arr)
        spages, svalid, inv = router.plan_streams(arr, self.num_rows,
                                                  self.num_shards)
        if valid is not None:
            v = np.asarray(valid, bool).reshape(-1)
            flat = svalid.reshape(-1)
            flat[inv] &= v
        with obs_tracing.span("shard.fused.dispatch", op="write",
                              pages=n, shards=self.num_shards):
            return _write_planned_jitted(self, jnp.asarray(spages),
                                         jnp.asarray(svalid),
                                         jnp.asarray(inv, jnp.int32), data)

    def migrate(self, src_pages, dst_pages, *,
                donate: bool = True) -> "ShardedPool":
        """Cross-shard relocation over the ``ppermute`` ring
        (see :func:`migrate_pages`)."""
        return migrate_pages(self, src_pages, dst_pages, donate=donate)

    def streams(self, pages, data=None, *, valid=None):
        """Bank-aligned ``(S, n)`` stream access (see :func:`read_streams`).

        With ``data=None`` reads, returning ``(S, n, page_words)`` still
        sharded over ``banks``; with ``data`` writes (``valid`` optionally
        masking entries) and returns the new pool.
        """
        if data is None:
            return read_streams(self, pages)
        return write_streams(self, pages, data, valid=valid)

    # -- deprecated access surface (thin shims over the unified API) --------

    def read_any(self, pages) -> jax.Array:
        pool_lib._warn_deprecated("read_any", "read(pages)")
        return read_any(self, pages)

    def read_any_status(self, pages) -> tuple[jax.Array, jax.Array]:
        pool_lib._warn_deprecated("read_any_status", "read(pages, status=True)")
        return read_any_status(self, pages)

    def write_any(self, pages, data: jax.Array) -> "ShardedPool":
        pool_lib._warn_deprecated("write_any", "write(pages, data)")
        return write_any(self, pages, data)

    def read_pages(self, pages) -> jax.Array:
        pool_lib._warn_deprecated("read_pages", "read(pages)")
        return self.read(pages)

    def read_pages_status(self, pages) -> tuple[jax.Array, jax.Array]:
        pool_lib._warn_deprecated("read_pages_status", "read(pages, status=True)")
        return self.read(pages, status=True)

    def write_pages(self, pages, data: jax.Array) -> "ShardedPool":
        pool_lib._warn_deprecated("write_pages", "write(pages, data)")
        return self.write(pages, data)

    def evict_prediction(self, new_boundary: int) -> list[int]:
        return evicted_extra_pages(self, new_boundary)

    def move_boundary(self, new_boundary: int) -> tuple["ShardedPool", dict]:
        return repartition(self, new_boundary)

    def set_daec_rows(self, daec_rows: int) -> "ShardedPool":
        return set_daec_rows(self, daec_rows)

    def read_writeback(self, pages):
        """Write-back read (see :meth:`repro.core.pool.PoolState.read_writeback`):
        corrected beats are persisted to the owning shard in the same pass.
        Returns ``(data, status, new_pool)``."""
        arr = pool_lib._as_page_array(self, pages)
        _note_dispatch("read_writeback", arr.shape[0])
        _memprof_routed(self, "gather", arr)
        return _read_writeback_jitted(self, arr)

    def scrub(self, use_kernel: bool = False):
        return scrub(self, use_kernel=use_kernel)

    def memprof_record(self, op: str, pages, stream: str = "main") -> None:
        """Feed one dispatch to CREAM-Lens, routed per shard (PoolLike)."""
        _memprof_routed(self, op, pages, stream)


def make_sharded_pool(num_rows: int, layout: Layout = Layout.INTERWRAP,
                      boundary: int | None = None, *, num_shards: int,
                      row_words: int = 64,
                      mesh: jax.sharding.Mesh | None = None,
                      use_kernel: bool | None = None,
                      daec_rows: int = 0) -> ShardedPool:
    """Create a zeroed sharded pool of ``num_rows`` *global* rows.

    ``boundary`` is the global CREAM-region size (default: whole pool in
    CREAM mode); both must shard evenly (multiples of
    ``num_shards * GROUP_ROWS``). ``daec_rows`` carves that many *global*
    top rows into the SEC-DAEC tier (must be a multiple of ``num_shards``
    and fit the protected region). ``mesh`` defaults to a fresh 1-D
    ``banks`` mesh over the first ``num_shards`` devices.
    """
    boundary = num_rows if boundary is None else boundary
    if layout == Layout.BASELINE_ECC:
        boundary = 0
    router.check_geometry(num_rows, boundary, num_shards)
    if daec_rows % num_shards:
        raise ValueError(
            f"daec_rows ({daec_rows}) must shard evenly over {num_shards}")
    if not 0 <= daec_rows <= num_rows - boundary:
        raise ValueError(
            f"daec_rows ({daec_rows}) must fit the protected region "
            f"[{boundary}, {num_rows})")
    if mesh is None:
        from repro.launch.mesh import make_banks_mesh
        mesh = make_banks_mesh(num_shards)
    if mesh.devices.size != num_shards or "banks" not in mesh.axis_names:
        raise ValueError(
            f"mesh must be a 1-D 'banks' mesh of {num_shards} devices")
    storage = jax.device_put(
        jnp.zeros((num_shards, num_rows // num_shards, LANES, row_words),
                  jnp.uint32),
        NamedSharding(mesh, P("banks")))
    return ShardedPool(storage, boundary // num_shards, layout, row_words,
                       mesh, use_kernel, daec_rows // num_shards)


def _local_state(state: ShardedPool, block: jax.Array) -> PoolState:
    """Per-shard view: ``block`` is the shard's ``(1, R_local, 9, W)`` slice."""
    return PoolState(block[0], state.boundary_local, state.layout,
                     state.row_words, state.daec_rows_local)


# ---------------------------------------------------------------------------
# General dispatch: arbitrary global page-id vectors
# ---------------------------------------------------------------------------


def read_any_status(state: ShardedPool, pages
                    ) -> tuple[jax.Array, jax.Array]:
    """Batch read + per-page status for arbitrary global page ids, fused.

    Every shard routes in-body (``axis_index`` ownership), reads its owned
    local ids through the mixed-pool engine, zeroes foreign rows, and one
    ``psum`` pair over ``banks`` assembles the replicated result — no
    stacked per-shard output, no owner-select chain. Traceable; returns
    ``(data (n, page_words) uint32, status (n,) int32)``.
    """
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)
    n = pages.shape[0]
    if n == 0:
        return (jnp.zeros((0, state.page_words), jnp.uint32),
                jnp.zeros((0,), jnp.int32))

    def body(block, pg):
        me = jax.lax.axis_index("banks")
        shard, local = router.route(pg, state.num_rows, state.num_shards)
        own = shard == me
        data, status = pool_lib.read_pages_any_status(
            _local_state(state, block), jnp.where(own, local, 0))
        return (jax.lax.psum(jnp.where(own[:, None], data, 0), "banks"),
                jax.lax.psum(jnp.where(own, status, 0), "banks"))

    return shard_map(
        body, mesh=state.mesh, in_specs=(P("banks"), P(None)),
        out_specs=(P(None), P(None)))(state.storage, pages)


def read_any(state: ShardedPool, pages) -> jax.Array:
    """Decode-corrected batch read: router fused into the kernel, one pass.

    Each shard dispatches the router-aware mixed kernel
    (:func:`repro.kernels.mixed.ops.read_correct_routed` — the Pallas
    scalar-prefetch index map composes the global-id -> (shard, local)
    translation with the layout translation; the jnp oracle elsewhere),
    zeroing rows it does not own, and a single ``psum`` over ``banks``
    assembles the replicated batch. Honours ``state.use_kernel``.
    """
    from repro.kernels.mixed import ops as mixed_ops
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)
    n = pages.shape[0]
    if n == 0:
        return jnp.zeros((0, state.page_words), jnp.uint32)

    if state.daec_rows_local > 0:
        # The fused mixed kernel corrects with SECDED only — a DAEC tier
        # would be mis-decoded. Route through the dual-codec engine instead.
        return read_any_status(state, pages)[0]

    def body(block, pg):
        me = jax.lax.axis_index("banks")
        data = mixed_ops.read_correct_routed(
            block[0], pg, state.layout, state.num_rows, state.boundary,
            state.num_shards, me, use_kernel=state.use_kernel)
        return jax.lax.psum(data, "banks")

    return shard_map(
        body, mesh=state.mesh, in_specs=(P("banks"), P(None)),
        out_specs=P(None))(state.storage, pages)


def write_any(state: ShardedPool, pages, data: jax.Array,
              valid=None) -> ShardedPool:
    """Code-maintaining batch write for arbitrary global page ids, fused.

    Each shard routes in-body and computes ownership from ``axis_index``;
    the engine's ``valid`` mask routes foreign (and caller-masked) pages'
    scatters out of range (dropped), so no collectives are needed — each
    shard's storage slice is written purely locally from the replicated
    data.
    """
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)
    n = pages.shape[0]
    if n == 0:
        return state
    data = data.astype(jnp.uint32).reshape(n, -1)
    if data.shape[1] != state.page_words:
        raise ValueError(f"page data must be {state.page_words} words")

    def body(block, pg, dat, *vld):
        me = jax.lax.axis_index("banks")
        shard, local = router.route(pg, state.num_rows, state.num_shards)
        own = shard == me
        if vld:
            own = own & vld[0]
        st = pool_lib.write_pages_any(_local_state(state, block), local, dat,
                                      valid=own)
        return st.storage[None]

    operands = (state.storage, pages, data)
    in_specs = [P("banks"), P(None), P(None)]
    if valid is not None:
        operands += (jnp.asarray(valid, bool).reshape(-1),)
        in_specs.append(P(None))
    storage = shard_map(
        body, mesh=state.mesh, in_specs=tuple(in_specs),
        out_specs=P("banks"))(*operands)
    return dataclasses.replace(state, storage=storage)


def read_any_writeback(state: ShardedPool, pages
                       ) -> tuple[jax.Array, jax.Array, ShardedPool]:
    """Write-back batch read for arbitrary global page ids, fused.

    Like :func:`read_any_status`, but each shard persists corrected beats
    of the pages it owns back into its own storage slice in the same pass
    (:func:`repro.core.pool.read_pages_any_writeback`); foreign pages are
    masked out of range so only the owner writes. Returns
    ``(data, status, new_pool)``.
    """
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)
    n = pages.shape[0]
    if n == 0:
        return (jnp.zeros((0, state.page_words), jnp.uint32),
                jnp.zeros((0,), jnp.int32), state)

    def body(block, pg):
        me = jax.lax.axis_index("banks")
        shard, local = router.route(pg, state.num_rows, state.num_shards)
        own = shard == me
        st = _local_state(state, block)
        data, status, st = pool_lib.read_pages_any_writeback(
            st, jnp.where(own, local, st.num_pages))
        return (jax.lax.psum(jnp.where(own[:, None], data, 0), "banks"),
                jax.lax.psum(jnp.where(own, status, 0), "banks"),
                st.storage[None])

    data, status, storage = shard_map(
        body, mesh=state.mesh, in_specs=(P("banks"), P(None)),
        out_specs=(P(None), P(None), P("banks")))(state.storage, pages)
    return data, status, dataclasses.replace(state, storage=storage)


_read_any_jitted = jax.jit(read_any)
_read_any_status_jitted = jax.jit(read_any_status)
_write_any_jitted = jax.jit(write_any, donate_argnums=(0,))
_read_writeback_jitted = jax.jit(read_any_writeback)


# ---------------------------------------------------------------------------
# Bank-parallel streams: the measured Figs. 9–11 hot path
# ---------------------------------------------------------------------------


def _read_streams_impl(state: ShardedPool, pages: jax.Array) -> jax.Array:
    # Local translation happens in-body on each shard's own (1, n) slice —
    # stream alignment guarantees ownership, so no shard id is needed.
    from repro.kernels.mixed import ops as mixed_ops

    if state.daec_rows_local > 0:
        # SECDED-only fused kernel would mis-decode the DAEC tier; fall
        # back to the dual-codec engine (same dispatch shape, jnp body).
        return _read_streams_status_impl(state, pages)[0]

    def body(block, pg):
        _, local = router.route(pg[0], state.num_rows, state.num_shards)
        data = mixed_ops.read_correct(
            block[0], local, state.layout, state.rows_local,
            state.boundary_local, use_kernel=state.use_kernel)
        return data[None]

    return shard_map(
        body, mesh=state.mesh, in_specs=(P("banks"), P("banks")),
        out_specs=P("banks"))(state.storage, pages)


def _read_streams_status_impl(state: ShardedPool, pages: jax.Array
                              ) -> tuple[jax.Array, jax.Array]:
    def body(block, pg):
        _, local = router.route(pg[0], state.num_rows, state.num_shards)
        data, status = pool_lib.read_pages_any_status(
            _local_state(state, block), local)
        return data[None], status[None]

    return shard_map(
        body, mesh=state.mesh, in_specs=(P("banks"), P("banks")),
        out_specs=(P("banks"), P("banks")))(state.storage, pages)


def _write_streams_impl(state: ShardedPool, pages: jax.Array,
                        data: jax.Array, valid=None) -> ShardedPool:
    def body(block, pg, dat, *vld):
        _, local = router.route(pg[0], state.num_rows, state.num_shards)
        st = pool_lib.write_pages_any(
            _local_state(state, block), local, dat[0].astype(jnp.uint32),
            valid=vld[0][0] if vld else None)
        return st.storage[None]

    operands = (state.storage, pages, data)
    in_specs = [P("banks"), P("banks"), P("banks")]
    if valid is not None:
        operands += (valid,)
        in_specs.append(P("banks"))
    storage = shard_map(
        body, mesh=state.mesh, in_specs=tuple(in_specs),
        out_specs=P("banks"))(*operands)
    return dataclasses.replace(state, storage=storage)


_read_streams_jitted = jax.jit(_read_streams_impl)
_write_streams_jitted = jax.jit(_write_streams_impl)


# The planned bank-aligned dispatch behind ShardedPool.read / .write:
# plan_streams (host numpy) regroups the batch into (S, m) per-bank streams
# + one inverse permutation; each program below is ONE jitted dispatch that
# gathers ~n/S pages per bank and permutes back to batch order on device.

def _read_planned_impl(state: ShardedPool, spages: jax.Array,
                       inv: jax.Array) -> jax.Array:
    data = _read_streams_impl(state, spages)
    return data.reshape(-1, state.page_words)[inv]


def _read_planned_status_impl(state: ShardedPool, spages: jax.Array,
                              inv: jax.Array
                              ) -> tuple[jax.Array, jax.Array]:
    data, status = _read_streams_status_impl(state, spages)
    return (data.reshape(-1, state.page_words)[inv],
            status.reshape(-1)[inv])


def _write_planned_impl(state: ShardedPool, spages: jax.Array,
                        svalid: jax.Array, inv: jax.Array,
                        data: jax.Array) -> ShardedPool:
    S, m = spages.shape
    sdata = jnp.zeros((S * m, state.page_words),
                      jnp.uint32).at[inv].set(data.astype(jnp.uint32))
    return _write_streams_impl(state, spages, sdata.reshape(S, m, -1),
                               valid=svalid)


_read_planned_jitted = jax.jit(_read_planned_impl)
_read_planned_status_jitted = jax.jit(_read_planned_status_impl)
_write_planned_jitted = jax.jit(_write_planned_impl, donate_argnums=(0,))


def read_streams(state: ShardedPool, pages: jax.Array) -> jax.Array:
    """Serve ``S`` independent request streams, one per bank, concurrently.

    ``pages`` is ``(S, n)`` *global* ids with stream ``s`` touching only
    shard ``s``'s pages (``page % S == s`` for regular pages) — the caller
    owns that alignment, mirroring how a bank-aware allocator hands each
    client its own rank subset. Each shard gathers only its own ``n`` pages
    (no masking, no replication, no collectives): per-bank work is ``n``
    pages regardless of ``S``, which is exactly the paper's bank-level
    parallelism claim. Returns ``(S, n, page_words)``, still sharded over
    ``banks``.

    Host wrapper around the jitted dispatch so CREAM-Lens can capture the
    aligned streams (stream ``bank<s>`` per shard); composes under an
    enclosing jit unchanged (the hook skips traced operands).
    """
    _memprof_routed(state, "gather", pages, stream="streams")
    return _read_streams_jitted(state, pages)


def write_streams(state: ShardedPool, pages: jax.Array,
                  data: jax.Array, valid=None) -> ShardedPool:
    """Per-bank scatter of ``S`` aligned streams (see :func:`read_streams`).

    ``pages`` is ``(S, n)`` shard-aligned global ids, ``data`` is
    ``(S, n, page_words)``; ``valid`` (optional ``(S, n)`` bool) drops
    masked entries via the engine's OOB-routing mask.
    """
    _memprof_routed(state, "scatter", pages, stream="streams")
    if valid is None:
        return _write_streams_jitted(state, pages, data)
    return _write_streams_jitted(state, pages, data,
                                 jnp.asarray(valid, bool))


# ---------------------------------------------------------------------------
# Cross-shard migration: explicit ppermute ring exchange
# ---------------------------------------------------------------------------


def _migrate_impl(state: ShardedPool, src: jax.Array, dst: jax.Array
                  ) -> ShardedPool:
    S = state.num_shards
    src_sh, src_lo = router.route(src, state.num_rows, S)
    dst_sh, dst_lo = router.route(dst, state.num_rows, S)
    ring = [(i, (i + 1) % S) for i in range(S)]

    def body(block, s_sh, s_lo, d_sh, d_lo):
        me = jax.lax.axis_index("banks")
        st = _local_state(state, block)
        data, _ = pool_lib.read_pages_any_status(st, s_lo)
        buf = jnp.where((s_sh == me)[:, None], data, 0)
        for step in range(S):
            if step:
                buf = jax.lax.ppermute(buf, "banks", ring)
            deliver = (s_sh == (me - step) % S) & (d_sh == me)
            st = pool_lib.write_pages_any(st, d_lo, buf, valid=deliver)
        return st.storage[None]

    storage = shard_map(
        body, mesh=state.mesh,
        in_specs=(P("banks"), P(None), P(None), P(None), P(None)),
        out_specs=P("banks"))(state.storage, src_sh, src_lo, dst_sh, dst_lo)
    return dataclasses.replace(state, storage=storage)


_migrate_jitted = jax.jit(_migrate_impl, donate_argnums=(0,))
_migrate_jitted_nodonate = jax.jit(_migrate_impl)


def migrate_pages(state: ShardedPool, src_pages, dst_pages,
                  donate: bool = True) -> ShardedPool:
    """Live in-pool migration ``src -> dst`` across shard boundaries.

    One fused dispatch: every shard decode-reads the source pages it owns,
    the page batch circulates the ``banks`` ring via ``S`` explicit
    ``ppermute`` steps (the rank-subset interconnect made visible), and at
    each step every shard lands the pages addressed to it with a masked
    code-maintaining write. Same-shard moves complete at step 0 without
    touching the ring. ``donate=False`` keeps the input pool's storage
    valid (benchmarks; callers that roll back).
    """
    src = pool_lib._as_page_array(state, src_pages)
    dst = pool_lib._as_page_array(state, dst_pages)
    fn = _migrate_jitted if donate else _migrate_jitted_nodonate
    if obs_metrics.enabled():
        obs_metrics.counter(
            obs_metrics.NAME_SHARD_RING_PAGES,
            "pages exchanged over the ppermute migration ring"
        ).inc(int(src.shape[0]))
    with obs_tracing.span("shard.migrate.ring", pages=int(src.shape[0]),
                          shards=state.num_shards):
        return fn(state, src, dst)


# ---------------------------------------------------------------------------
# Repartitioning: all shards move their boundary register in lockstep
# ---------------------------------------------------------------------------


def evicted_extra_pages(state: ShardedPool, new_boundary: int) -> list[int]:
    """Global extra-page ids a move to ``new_boundary`` would evict.

    Round-robin extra striping makes the surviving set a contiguous global
    prefix, so — exactly as for the local pool — the evicted ids are the
    trailing range.
    """
    if new_boundary >= state.boundary:
        return []
    x_new = extra_page_count(state.layout,
                             new_boundary // state.num_shards,
                             state.row_words)
    return list(range(state.num_rows + state.num_shards * x_new,
                      state.num_rows + state.num_extra_pages))


def repartition(state: ShardedPool, new_boundary: int
                ) -> tuple[ShardedPool, dict]:
    """Move every shard's CREAM/SECDED boundary in lockstep.

    Semantics mirror :func:`repro.core.pool.repartition` (page contents of
    surviving ids preserved, codes re-established, evicted extras reported);
    the data plane is one ``shard_map`` over the local repartition, so each
    bank re-encodes its own span independently — no cross-shard traffic.
    """
    router.check_geometry(state.num_rows, new_boundary, state.num_shards)
    old = state.boundary
    info = {"old_boundary": old, "new_boundary": new_boundary,
            "evicted_extra_pages": [], "pages_reencoded": 0}
    if new_boundary == old:
        return state, info
    info["evicted_extra_pages"] = evicted_extra_pages(state, new_boundary)
    info["pages_reencoded"] = abs(new_boundary - old)
    nb_local = new_boundary // state.num_shards

    def body(block):
        new_st, _ = pool_lib.repartition(_local_state(state, block), nb_local)
        return new_st.storage[None]

    with obs_tracing.span("shard.repartition", old_boundary=old,
                          new_boundary=new_boundary,
                          shards=state.num_shards):
        storage = jax.jit(shard_map(
            body, mesh=state.mesh, in_specs=P("banks"),
            out_specs=P("banks")))(state.storage)
    return dataclasses.replace(state, storage=storage,
                               boundary_local=nb_local), info


def set_daec_rows(state: ShardedPool, daec_rows: int) -> ShardedPool:
    """Resize the SEC-DAEC tier: every shard re-encodes its own top span.

    ``daec_rows`` is global and must shard evenly; semantics per shard
    mirror :func:`repro.core.pool.set_daec_rows` (contents preserved —
    decode under the old codec, re-encode under the new one).
    """
    S = state.num_shards
    if daec_rows % S:
        raise ValueError(
            f"daec_rows ({daec_rows}) must shard evenly over {S}")
    if not 0 <= daec_rows <= state.num_rows - state.boundary:
        raise ValueError(
            f"daec_rows ({daec_rows}) must fit the protected region "
            f"[{state.boundary}, {state.num_rows})")
    n_local = daec_rows // S
    if n_local == state.daec_rows_local:
        return state

    def body(block):
        st = pool_lib.set_daec_rows(_local_state(state, block), n_local)
        return st.storage[None]

    with obs_tracing.span("shard.set_daec_rows", old=state.daec_rows,
                          new=daec_rows, shards=S):
        storage = jax.jit(shard_map(
            body, mesh=state.mesh, in_specs=P("banks"),
            out_specs=P("banks")))(state.storage)
    return dataclasses.replace(state, storage=storage,
                               daec_rows_local=n_local)


# ---------------------------------------------------------------------------
# Scrubbing (background sweep; per-shard, host-driven)
# ---------------------------------------------------------------------------


def scrub(state: ShardedPool, use_kernel: bool = False):
    """Sweep every shard, repairing in place; returns (state', ScrubStats).

    Background path (not latency-critical): shards are swept sequentially
    host-side and the per-shard censuses merged, with corrupt row ids mapped
    back to global rows (``global = local * S + shard``).
    """
    from repro.core.scrubber import ScrubStats
    from repro.core.scrubber import scrub as _scrub
    S = state.num_shards
    blocks, merged, corrupt = [], {}, []
    for s in range(S):
        st = PoolState(state.storage[s], state.boundary_local, state.layout,
                       state.row_words, state.daec_rows_local)
        new_st, stats = _scrub(st, use_kernel=use_kernel)
        blocks.append(new_st.storage)
        for f in ("beats_checked", "corrected_data", "corrected_code",
                  "detected_uncorrectable", "parity_lines_checked",
                  "parity_corrupt_lines", "latent_errors_killed"):
            merged[f] = merged.get(f, 0) + getattr(stats, f)
        corrupt.extend(r * S + s for r in stats.corrupt_rows)
    storage = jax.device_put(jnp.stack(blocks),
                             NamedSharding(state.mesh, P("banks")))
    return (dataclasses.replace(state, storage=storage),
            ScrubStats(corrupt_rows=tuple(sorted(corrupt)), **merged))
