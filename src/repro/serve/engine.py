"""CREAM-Serve: continuous batching with KV paged onto the CREAM pool.

Paper anchor: §6.1 / Fig. 8 — the end-to-end capacity claim (memcached
+23.0 %, WebSearch +37.3 %) restated for LLM serving: the KV cache IS the
capacity-sensitive working set, stored page-for-page in a CREAM pool, and
the boundary register's reclaimed code-lane pages are extra sequences
served without a host round-trip.

The engine is vLLM-shaped but the data plane is this repo's:

  * every (sequence, layer, KV block) lives in one CREAM pool page; the
    :class:`repro.serve.paged_kv.PagedKV` block table maps them and the
    :class:`repro.serve.scheduler.Scheduler` decides residency
    (admission, parking between turns, preempt-to-host under pressure);
  * a decode step is exactly three dispatches on any
    :class:`repro.core.pool.PoolLike` (local or CREAM-Shard): ONE batched
    page gather (``pool.read`` with the flattened block tables as index
    map — on a sharded pool the planned bank-aligned dispatch, ~``n/S``
    pages per bank), one fused model step
    (:func:`repro.models.transformer.decode_step_paged` over all slots,
    optionally fused with the ``ppermute`` migration ring so scheduled
    page moves overlap the attention compute), and ONE batched scatter of
    the updated current blocks (``pool.write``). No Python per-sequence
    loop touches KV;
  * prefill extracts the prompt's KV from the dense
    :func:`repro.models.transformer.prefill` state and packs it into the
    sequence's blocks with a single batched write.

All shapes are fixed by ``(max_batch, n_layers, max_blocks)``: unbound
slots read and write a scratch page and are masked by ``cache_len = 0``,
so the whole serving loop runs three compiled programs regardless of which
sequences are live.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import pool as pool_lib
from repro.core import secded
from repro.core.layouts import Layout
from repro.core.pool import PoolState
from repro.kernels.mixed import ops as mixed_ops
from repro.models import build_model
from repro.models import transformer
from repro.obs import memprof as obs_memprof
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.serve.paged_kv import PagedKV, token_words_for
from repro.serve.scheduler import Scheduler, ServeRequest
from repro.vm.address_space import VirtualMemory

# Re-export: the old engine's request type moved to the scheduler.
Request = ServeRequest


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def _cream_cls_index(layout: Layout) -> int:
    """Index into :data:`repro.obs.metrics.FOLD_CLASSES` for CREAM pages."""
    if layout == Layout.BASELINE_ECC:
        return obs_metrics.FOLD_CLASSES.index("secded")
    cls = "parity" if layout == Layout.PARITY else "none"
    return obs_metrics.FOLD_CLASSES.index(cls)


def _status_counts(pages: jax.Array, status: jax.Array, boundary: int,
                   num_rows: int, cream_idx: int,
                   daec_start: int) -> jax.Array:
    """Per-class (corrected, uncorrectable) counts — the device-side
    accumulator the registry folds between steps. Shape
    ``(len(FOLD_CLASSES), 2)`` int32, rows indexed by ``FOLD_CLASSES`` —
    derived from the Protection ladder, never a literal."""
    classes = obs_metrics.FOLD_CLASSES
    is_sec = (pages >= boundary) & (pages < num_rows)
    cls = jnp.where(is_sec, classes.index("secded"), cream_idx)
    cls = jnp.where(is_sec & (pages >= daec_start),
                    classes.index("daec"), cls)
    corrected = ((status == secded.CORRECTED_DATA)
                 | (status == secded.CORRECTED_CODE)).astype(jnp.int32)
    unc = (status == secded.DETECTED_UNCORRECTABLE).astype(jnp.int32)
    counts = jnp.zeros((len(classes), 2), jnp.int32)
    counts = counts.at[cls, 0].add(corrected)
    return counts.at[cls, 1].add(unc)


@jax.jit
def _read_correct_counts(state: PoolState, pages: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """Metrics-enabled gather for a local pool: the SAME fused mixed-pool
    read the plain path uses, except the per-page status it already
    computes is kept and reduced to the per-class count matrix inside
    the same compiled program — still one gather dispatch per step."""
    data, status = pool_lib.read_pages_any_status(state, pages)
    counts = _status_counts(pages, status, state.boundary, state.num_rows,
                            _cream_cls_index(state.layout),
                            state.daec_start)
    return data, counts


@functools.partial(jax.jit,
                   static_argnames=("boundary", "num_rows", "cream_idx",
                                    "daec_start"))
def _counts_only(pages: jax.Array, status: jax.Array, boundary: int,
                 num_rows: int, cream_idx: int,
                 daec_start: int) -> jax.Array:
    return _status_counts(pages, status, boundary, num_rows, cream_idx,
                          daec_start)


class Engine:
    """Paged-KV continuous-batching engine on a CREAM pool.

    ``mode='cream'`` runs the pool boundary-free (InterWrap, +12.5 %
    pages); ``'secded'`` pins ``boundary=0`` (all rows SECDED — the
    conventional-ECC baseline with the same arithmetic). Pass an existing
    ``vm`` (with pool ``pool`` already added, possibly sharded) to share
    the data plane with other tenants; the engine never branches on the
    pool's concrete type.
    """

    def __init__(self, cfg: ModelConfig, max_batch: int, max_len: int,
                 vm: VirtualMemory | None = None, pool: str = "kv",
                 mode: str = "cream", num_rows: int = 64,
                 row_words: int = 64, max_sessions: int = 128,
                 secded_rows: int = 0, seed: int = 0):
        if mode not in ("cream", "secded"):
            raise ValueError(mode)
        if len(transformer.attn_pattern_positions(cfg)) != len(cfg.pattern):
            raise ValueError(f"{cfg.name}: CREAM-Serve pages KV only; "
                             "attention-only patterns required")
        if vm is None:
            vm = VirtualMemory(row_words=row_words)
            # cream: boundary-free pool, except `secded_rows` kept in the
            # SECDED region so paid-tier requests have frames of their class
            vm.add_pool(pool, num_rows, Layout.INTERWRAP,
                        boundary=num_rows - secded_rows
                        if mode == "cream" else 0)
        self.cfg = cfg
        self.vm = vm
        self.pool_name = pool
        self.mode = mode
        self.max_batch = max_batch
        self.max_len = max_len
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.key(seed))
        self.n_layers = transformer.num_attn_layers(cfg)
        self.kv = PagedKV(
            vm, pool, n_layers=self.n_layers,
            token_words=token_words_for(cfg.num_kv_heads, cfg.head_dim_,
                                        cfg.activation_dtype),
            max_seqs=max_sessions, max_tokens=max_len)
        self.sched = Scheduler(self.kv, max_batch, token_limit=max_len)
        # host-side per-slot decode registers
        self._lens = np.zeros(max_batch, np.int32)
        self._toks = np.zeros(max_batch, np.int32)
        self.steps = 0
        # cumulative over decode steps: pages the gather read, those of
        # them that hold live tokens (the rest is block-table padding), and
        # those in the SECDED region (the ones the gather decodes)
        self.pages_gathered = 0
        self.pages_gathered_live = 0
        self.pages_gathered_secded = 0
        self._prefill = jax.jit(
            lambda p, toks: self.model.prefill(p, toks, max_len))
        self._attend = jax.jit(self._attend_fn)
        # attend fused with the ppermute migration ring: ONE program, so
        # XLA overlaps the ring's collectives with the attention matmuls
        # (separate dispatches on the same devices would serialise)
        self._attend_ring = jax.jit(self._attend_ring_fn,
                                    donate_argnums=(4,))
        self._pending_migration: tuple[np.ndarray, np.ndarray] | None = None
        self._pack = jax.jit(self._pack_fn)
        # the paged-attention gather: the kernels/mixed fused read with the
        # flattened block table as its scalar-prefetched index map (geometry
        # is static → one compile per pool mode, page ids stay dynamic)
        self._mixed_read = jax.jit(
            mixed_ops.read_correct,
            static_argnames=("layout", "num_rows", "boundary",
                             "use_kernel"))
        if obs_metrics.enabled():
            # pre-create the acceptance-critical series at zero so every
            # snapshot carries the full per-class matrix, errors or not
            obs_metrics.touch_read_status()
            mig = obs_metrics.counter(
                obs_metrics.NAME_PAGES_MIGRATED,
                "pages relocated by the migration engine", labels=("cls",))
            for cls in obs_metrics.FOLD_CLASSES:
                mig.labels(cls=cls)
            obs_metrics.counter(
                obs_metrics.NAME_DECODE_STEPS,
                "batched decode steps executed")
            obs_metrics.counter(
                obs_metrics.NAME_TOKENS_DECODED,
                "tokens decoded, by request tier", labels=("tier",))
            obs_metrics.counter(
                obs_metrics.NAME_PREFILLS, "prompt prefills executed")
        obs_metrics.record_pool_capacity(pool, self.pool)

    # -- geometry shorthands -------------------------------------------------
    @property
    def pool(self):
        return self.vm.pools[self.pool_name]

    @property
    def _bt(self) -> int:
        return self.kv.block_tokens

    @property
    def _s_pad(self) -> int:
        return self.kv.max_blocks * self.kv.block_tokens

    # -- the fused per-step compute (one compiled program) -------------------
    def _attend_fn(self, params, pages_u32, lens, toks):
        """(B*L*maxB, page_words) gathered pages -> (logits, next token,
        updated current-block pages (B*L, page_words))."""
        cfg, kvw = self.cfg, self.kv.kv_words
        B, L, maxB, bt = (self.max_batch, self.n_layers,
                          self.kv.max_blocks, self._bt)
        hkv, hd = cfg.num_kv_heads, cfg.head_dim_
        pages = pages_u32.reshape(B, L, maxB, -1)
        kvv = jax.lax.bitcast_convert_type(pages[..., :kvw], jnp.float32)
        kvv = kvv.reshape(B, L, maxB, 2, bt, hkv, hd)
        k = kvv[:, :, :, 0].transpose(1, 0, 2, 3, 4, 5) \
            .reshape(L, B, maxB * bt, hkv, hd)
        v = kvv[:, :, :, 1].transpose(1, 0, 2, 3, 4, 5) \
            .reshape(L, B, maxB * bt, hkv, hd)
        logits, _, (k_new, v_new) = transformer.decode_step_paged(
            params, cfg, {"cache_len": lens}, toks, (k, v))
        # write-back: pick each slot's current block as B*L whole page rows
        # (row (b*L + l)*maxB + blk[b]), then insert the new token into them
        blk = lens // bt
        off = lens - blk * bt
        rows = jnp.arange(B * L).reshape(B, L) * maxB + blk[:, None]
        cur = jnp.take(pages_u32, rows.reshape(-1), axis=0)   # (B*L, pw)
        curr = jax.lax.bitcast_convert_type(cur[:, :kvw], jnp.float32) \
            .reshape(B, L, 2, bt, hkv, hd)
        new_tok = jnp.stack([k_new.transpose(1, 0, 2, 3),
                             v_new.transpose(1, 0, 2, 3)], axis=2)
        onehot = jnp.arange(bt) == off[:, None]              # (B, bt)
        curr = jnp.where(onehot[:, None, None, :, None, None],
                         new_tok[:, :, :, None], curr)
        cur_used = jax.lax.bitcast_convert_type(curr, jnp.uint32) \
            .reshape(B * L, kvw)
        cur_pages = jnp.concatenate([cur_used, cur[:, kvw:]], axis=-1)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, nxt, cur_pages

    def _attend_ring_fn(self, params, pages_u32, lens, toks, pool, src, dst):
        """:meth:`_attend_fn` fused with the sharded pool's ``ppermute``
        migration ring in ONE compiled program — the ring's cross-bank
        exchange overlaps the attention compute instead of serialising
        after it. ``pool``'s storage is donated (the caller installs the
        returned pool). Contract: ``src``/``dst`` must not touch pages of
        bound decode sequences (scheduled migrations are screened by
        :meth:`schedule_migration`'s caller)."""
        from repro.shard.pool import _migrate_impl
        logits, nxt, cur_pages = self._attend_fn(params, pages_u32, lens,
                                                 toks)
        return logits, nxt, cur_pages, _migrate_impl(pool, src, dst)

    def schedule_migration(self, src_pages, dst_pages) -> None:
        """Queue a page migration to run overlapped with the next decode
        step's compute (sharded pools: fused into the attend program so the
        ring's ``ppermute`` steps interleave with the matmuls; local pools:
        one fused migrate dispatch after compute). The pages must not
        belong to bound decode sequences — relocating a bound page would
        race the step's scatter; park or preempt the sequence first and
        call :meth:`refresh_translation` after the step."""
        src = np.asarray(src_pages, np.int32).reshape(-1)
        dst = np.asarray(dst_pages, np.int32).reshape(-1)
        if src.shape != dst.shape:
            raise ValueError("src/dst page lists must match")
        if self._pending_migration is not None:
            src = np.concatenate([self._pending_migration[0], src])
            dst = np.concatenate([self._pending_migration[1], dst])
        self._pending_migration = (src, dst)

    def _pack_fn(self, k, v):
        """Prefill KV (L, S, Hkv, D) pair -> (L*maxB, page_words) pages."""
        L, maxB, bt = self.n_layers, self.kv.max_blocks, self._bt
        pad = self._s_pad - k.shape[1]
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv = jnp.stack([k.reshape(L, maxB, bt, *k.shape[2:]),
                        v.reshape(L, maxB, bt, *v.shape[2:])], axis=2)
        used = jax.lax.bitcast_convert_type(kv, jnp.uint32) \
            .reshape(L, maxB, self.kv.kv_words)
        tail = jnp.zeros((L, maxB, self.kv.page_words - self.kv.kv_words),
                         jnp.uint32)
        return jnp.concatenate([used, tail], axis=-1) \
            .reshape(L * maxB, self.kv.page_words)

    def _gather_pages(self, phys: np.ndarray) -> jax.Array:
        """The decode step's ONE page gather. Local pools take the
        :mod:`repro.kernels.mixed` fused read — the Pallas scalar-prefetch
        kernel on TPU, its vectorised jnp oracle (= the mixed-pool engine's
        fast path) on CPU; sharded pools take the planned bank-aligned
        dispatch behind ``pool.read`` (host stream planning + ONE jitted
        per-bank gather, ~``n/S`` pages per bank)."""
        pool = self.pool
        if isinstance(pool, PoolState) and pool.daec_rows == 0:
            # the fused read bypasses the pool's wrappers, so feed
            # CREAM-Lens here (sharded pools record inside pool.read).
            # A DAEC tier falls through to pool.read — the mixed kernel
            # corrects with SECDED only and would mis-decode those rows.
            pool.memprof_record("gather", phys, stream="decode")
            return self._mixed_read(pool.storage,
                                    jnp.asarray(phys, jnp.int32),
                                    layout=pool.layout,
                                    num_rows=pool.num_rows,
                                    boundary=pool.boundary)
        return pool.read(phys)

    def _gather_pages_counts(self, phys: np.ndarray
                             ) -> tuple[jax.Array, jax.Array]:
        """Metrics-enabled gather: same dispatch shape, plus the (3, 2)
        per-class status-count matrix carried out of jit for the registry
        fold (see :func:`repro.obs.metrics.fold_read_status`)."""
        pool = self.pool
        pages = jnp.asarray(phys, jnp.int32)
        if isinstance(pool, PoolState):
            pool.memprof_record("gather", phys, stream="decode")
            return _read_correct_counts(pool, pages)
        data, status = pool.read(phys, status=True)
        counts = _counts_only(pages, status, boundary=pool.boundary,
                              num_rows=pool.num_rows,
                              cream_idx=_cream_cls_index(pool.layout),
                              daec_start=pool.daec_start)
        return data, counts

    # -- request intake ------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        self.sched.submit(req)

    def refresh_translation(self) -> list[int]:
        """Call after an external repartition/migration on the serve pool:
        refreshes the block tables' physical mirror and preempts bound
        sequences whose pages left the device. Returns the dropped slots."""
        return self.sched.sync_residency()

    # -- the serving loop ------------------------------------------------------
    def _do_prefill(self, slot: int, req: ServeRequest, sess) -> None:
        with obs_tracing.span("engine.prefill", seq_id=req.seq_id,
                              prompt=len(req.prompt), tier=req.tier):
            self._do_prefill_impl(slot, req, sess)
        if obs_metrics.enabled():
            obs_metrics.counter(obs_metrics.NAME_PREFILLS,
                                "prompt prefills executed").inc()

    def _do_prefill_impl(self, slot: int, req: ServeRequest, sess) -> None:
        toks = jnp.asarray(np.asarray(req.prompt)[None, :], jnp.int32)
        logits, state = self._prefill(self.params, toks)
        apos = transformer.attn_pattern_positions(self.cfg)
        ks = jnp.stack([state[f"pos{i}"]["k"][:, 0] for i in apos], axis=1)
        vs = jnp.stack([state[f"pos{i}"]["v"][:, 0] for i in apos], axis=1)
        sh = (self.n_layers,) + ks.shape[2:]
        pages = self._pack(ks.reshape(sh).astype(jnp.float32),
                           vs.reshape(sh).astype(jnp.float32))
        p = len(req.prompt)
        nb = self.kv.blocks_for(p)
        phys = self.kv.gather_phys(np.asarray([sess.row]))[0]   # (L, maxB)
        ids = phys[:, :nb].reshape(-1)
        data = pages.reshape(self.n_layers, self.kv.max_blocks, -1)[:, :nb] \
            .reshape(len(ids), -1)
        self.vm.pools[self.pool_name] = self.pool.write(ids, data)
        sess.cache_len = p
        sess.last_tok = int(jnp.argmax(logits[0, -1]))
        if not req.t_first:
            req.t_first = time.perf_counter()
        req.generated.append(sess.last_tok)
        self._lens[slot] = sess.cache_len
        self._toks[slot] = sess.last_tok

    def step(self) -> list[ServeRequest]:
        """One decode step over every bound slot: one page gather, one
        model dispatch, one page scatter. Returns requests that finished.

        Its spans (``engine.step`` and the children ``.plan``, ``.gather``,
        ``.compute`` or ``.compute_ring``, ``.scatter``, ``.sync``,
        ``.emit``) each cover host work the step does anyway; only
        ``.sync``, the read of the next tokens, waits for the device."""
        with obs_tracing.span("engine.step"):
            with obs_tracing.span("engine.step.plan"):
                self.sched.ensure_step()
                if obs_memprof.enabled():
                    obs_memprof.next_step()   # one per decode step
                rows = np.asarray([s.row if s is not None else -1
                                   for s in self.sched.slots])
                active = rows >= 0
                if not active.any():
                    return []
                lens = np.where(active, self._lens, 0).astype(np.int32)
                toks = np.where(active, self._toks, 0).astype(np.int32)
                phys = self.kv.gather_phys(rows)                # (B, L, maxB)
                self.pages_gathered += int(phys.size)
                # live: blocks < ceil((len + 1) / block_tokens) of bound slots
                self.pages_gathered_live += self.n_layers * int(
                    (-(-(lens[active] + 1) // self._bt)).sum())
                secded = int(((phys >= self.pool.boundary)
                              & (phys < self.pool.num_rows)).sum())
                self.pages_gathered_secded += secded
            counts = None
            with obs_tracing.span("engine.step.gather", pages=int(phys.size),
                                  secded=secded):
                if obs_metrics.enabled():
                    pages, counts = self._gather_pages_counts(phys.reshape(-1))
                else:
                    pages = self._gather_pages(phys.reshape(-1))  # ONE gather
            pending = self._pending_migration
            from repro.shard.pool import ShardedPool
            if pending is not None and isinstance(self.pool, ShardedPool):
                # ring overlapped with compute: ONE fused program
                src, dst = pending
                self._pending_migration = None
                if obs_metrics.enabled():
                    obs_metrics.counter(
                        obs_metrics.NAME_SHARD_RING_PAGES,
                        "pages exchanged over the ppermute migration ring"
                    ).inc(int(src.shape[0]))
                with obs_tracing.span("engine.step.compute_ring",
                                      ring_pages=int(src.shape[0])):
                    _, nxt, cur_pages, new_pool = self._attend_ring(
                        self.params, pages, jnp.asarray(lens),
                        jnp.asarray(toks), self.pool,
                        jnp.asarray(src), jnp.asarray(dst))
                    self.vm.pools[self.pool_name] = new_pool
            else:
                with obs_tracing.span("engine.step.compute"):
                    _, nxt, cur_pages = self._attend(self.params, pages,
                                                     jnp.asarray(lens),
                                                     jnp.asarray(toks))
                if pending is not None:
                    self._pending_migration = None
                    self.vm.pools[self.pool_name] = self.pool.migrate(
                        pending[0], pending[1])
            with obs_tracing.span("engine.step.scatter"):
                cur_ids = self.kv.current_block_phys(rows, lens)  # (B, L)
                self.vm.pools[self.pool_name] = self.pool.write(
                    cur_ids.reshape(-1), cur_pages)             # ONE scatter
            with obs_tracing.span("engine.step.sync"):
                nxt = np.asarray(nxt)
            self.steps += 1
            with obs_tracing.span("engine.step.emit"):
                if counts is not None:
                    obs_metrics.fold_read_status(counts)
                finished = []
                tokens_by_tier: dict[str, int] = {}
                for slot in np.flatnonzero(active):
                    sess = self.sched.slots[slot]
                    sess.cache_len += 1
                    sess.last_tok = int(nxt[slot])
                    if not sess.req.t_first:
                        sess.req.t_first = time.perf_counter()
                    sess.req.generated.append(sess.last_tok)
                    self._lens[slot] = sess.cache_len
                    self._toks[slot] = sess.last_tok
                    tier = sess.req.tier
                    tokens_by_tier[tier] = tokens_by_tier.get(tier, 0) + 1
                    if len(sess.req.generated) >= sess.req.max_new:
                        finished.append(self.sched.finish(slot))
            if obs_metrics.enabled():
                obs_metrics.counter(obs_metrics.NAME_DECODE_STEPS,
                                    "batched decode steps executed").inc()
                tok = obs_metrics.counter(
                    obs_metrics.NAME_TOKENS_DECODED,
                    "tokens decoded, by request tier", labels=("tier",))
                for tier, n in tokens_by_tier.items():
                    tok.labels(tier=tier).inc(n)
            return finished

    def poll(self) -> list[ServeRequest]:
        """One serving-loop iteration: an admission pass (prefilling the
        newly admitted sessions) followed by one batched decode step.
        Returns requests that completed; raises on an unserveable queue."""
        with obs_tracing.span("engine.poll"):
            admitted = self.sched.tick()
            done: list[ServeRequest] = []
            for adm in admitted:
                if adm.is_prefill:
                    self._do_prefill(adm.slot, adm.req, adm.session)
                    if len(adm.req.generated) >= adm.req.max_new:
                        done.append(self.sched.finish(adm.slot))
                else:
                    self._lens[adm.slot] = adm.session.cache_len
                    self._toks[adm.slot] = adm.session.last_tok
            if self.sched.active_slots():
                done.extend(self.step())
            elif not admitted and self.sched.waiting:
                raise RuntimeError(
                    "deadlock: waiting requests cannot be admitted "
                    f"({self.sched.stats})")
        return done

    def serve(self, requests: list[ServeRequest]) -> dict:
        """Serve a request list to completion; returns the run's stats."""
        for req in requests:
            self.submit(req)
        done: list[ServeRequest] = []
        t0 = time.perf_counter()
        while self.sched.has_work():
            done.extend(self.poll())
        wall = time.perf_counter() - t0
        lats = [r.latency_s for r in done]
        tokens = sum(len(r.generated) for r in done)
        return {
            "wall_s": wall,
            "tokens": tokens,
            "tokens_per_s": tokens / wall if wall else 0.0,
            "requests": len(done),
            "p50_latency_ms": _percentile(lats, 50) * 1e3,
            "p99_latency_ms": _percentile(lats, 99) * 1e3,
            "decode_steps": self.steps,
            "device_pages": self.vm.device_capacity_pages(self.pool_name),
            "device_util": self.vm.utilisation(self.pool_name),
            "vm_fault_rate": self.vm.stats.fault_rate,
            "host_reads": self.vm.stats.host_reads,
            "mode": self.mode,
            **self.sched.stats,
        }
