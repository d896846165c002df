"""One benchmark run of one cell: set-up, the measured window, the check.

The system under test is ``repro.serve.Engine``, driven only through
``submit`` and ``poll``; everything else here is the benchmark's own:
the traffic, the weights (drawn from the seed and handed to the engine),
the clock, the trace annotations and the reference that decides
``correct``. See ``run.py`` for the command line.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import manifest
from traffic import gen

HERE = manifest.HERE
ROOT = os.path.dirname(os.path.dirname(HERE))
#: How long after the window's close an open loop's requests due in the
#: window may still take to get their first token (the wait counts).
DRAIN_S = 60.0
#: Sessions compared with the reference, besides the longest.
SAMPLE = 16
#: Tokens decoded by the post-window turn that reads the flipped page.
FLIP_TOKENS = 8
#: Bits flipped in one word of the paid (SECDED) session's page: one
#: exponent bit, which SECDED must correct.
FLIP_BITS = (30,)
#: Session id of the check's own paid turn after the window.
FLIP_SID = "flipcheck"


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Req:
    sid: str
    tier: str
    sched: float                 # scheduled arrival, s after t0
    submit: float = math.nan     # when the benchmark submitted it
    first_poll: float = math.nan  # start of the poll that gave token 1
    stamps: list = dataclasses.field(default_factory=list)
    obj: object = None           # the engine's ServeRequest
    seen: int = 0                # tokens already stamped
    done: bool = False


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_key(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(int(seed))


# -- set-up -----------------------------------------------------------------

def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def program_config(cell: manifest.Cell):
    from repro.configs import get_config
    prog = cell.config["program"]
    cfg = dataclasses.replace(get_config(prog["config"]),
                              **prog.get("overrides", {}))
    pub, ref = cell.config["published"], manifest.reference(cell.config)
    m = ref.dims(pub)
    got = {"L": cfg.num_layers, "d": cfg.d_model, "hq": cfg.num_heads,
           "hkv": cfg.num_kv_heads, "hd": cfg.head_dim_, "ff": cfg.d_ff,
           "V": cfg.vocab_size, "eps": cfg.norm_eps,
           "theta": float(cfg.rope_theta), "qk_norm": cfg.qk_norm,
           "tied": cfg.tie_embeddings}
    differ = {k: (got[k], m[k]) for k in m if got[k] != m[k]}
    if differ:
        raise ValueError(f"program config departs from the published "
                         f"sizes (program, published): {differ}")
    return cfg


def build_engine(cell: manifest.Cell, cfg, devices):
    from repro.core.layouts import Layout
    from repro.serve import Engine
    from repro.vm.address_space import VirtualMemory
    c, pool = cell.config, cell.config["pool"]
    vm = VirtualMemory(row_words=pool["row_words"])
    kw = {}
    if pool.get("shards", 1) > 1:
        import jax
        kw["mesh"] = jax.make_mesh((pool["shards"],), ("banks",),
                                   devices=devices[:pool["shards"]],
                                   axis_types=(jax.sharding.AxisType.Auto,))
    vm.add_pool("kv", pool["num_rows"], Layout.INTERWRAP,
                boundary=pool["num_rows"] - pool["secded_rows"],
                shards=pool.get("shards", 1), **kw)
    return Engine(cfg, max_batch=c["max_batch"], max_len=c["max_len"], vm=vm,
                  max_sessions=c["max_sessions"], seed=0)


def install_weights(eng, cell: manifest.Cell, seed: int):
    """Draw the weights from the seed (one jitted call on the device) and
    hand them to the engine in place of the ones it made itself."""
    import jax
    ref = manifest.reference(cell.config)
    want = dict(ref.flatten(ref.weight_shapes(cell.config["published"])))
    have = {"/".join(str(k.key) for k in path): tuple(leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                eng.params)[0]}
    if have != want:
        raise ValueError("the engine's weights are not laid out as the "
                         f"reference's: {sorted(set(have) ^ set(want))}")
    eng.params = None
    gc.collect()
    key = jax.random.key(int(seed_key(seed).generate_state(1)[0]))
    weights = ref.make_weights(cell.config["published"], key)
    jax.block_until_ready(weights)
    eng.params = weights
    return weights


def check_pallas_gather(eng) -> None:
    """The decode gather must be the fused Pallas mixed read: obs metrics
    (which switch it to a jnp path) are off, and its lowering holds the
    TPU custom call."""
    import jax.numpy as jnp
    from repro.obs import metrics, tracing
    if metrics.enabled() or tracing.enabled():
        raise RuntimeError("obs metrics or tracing are on")
    pool = eng.pool
    if not hasattr(pool, "boundary") or getattr(pool, "num_shards", 1) > 1:
        return
    n = eng.max_batch * eng.n_layers * eng.kv.max_blocks
    text = eng._mixed_read.lower(pool.storage, jnp.zeros((n,), jnp.int32),
                                 layout=pool.layout, num_rows=pool.num_rows,
                                 boundary=pool.boundary).as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("the decode gather holds no Pallas call")


def close(eng, sid: str) -> None:
    if sid in eng.sched.sessions:
        eng.sched.close_session(sid)


def warm_up(eng, cell: manifest.Cell, lens: list[int]) -> dict:
    """Run every shape this run's traffic can use through the engine:
    each prompt length it draws (prefill, pack, page write) with one
    decode step (gather, attend, scatter) and, where sessions are reused,
    the swap tier's page-count shapes (preempt to host and restore)."""
    from repro.serve import ServeRequest
    vocab = cell.config["published"]["vocab_size"]
    rng = np.random.default_rng(0)
    for i in range(0, len(lens), eng.max_batch):
        batch = [ServeRequest(f"warm{j}", gen.prompt_tokens(rng, n, vocab),
                              2, tier="batch")
                 for j, n in enumerate(lens[i:i + eng.max_batch], i)]
        for r in batch:
            eng.submit(r)
        while eng.sched.has_work():
            eng.poll()
        for r in batch:
            close(eng, r.seq_id)
    swaps = 0
    if "sessions" in cell.mix:
        kv = eng.kv
        lo = kv.blocks_for(min(lens))
        for nb in range(lo, kv.max_blocks + 1):
            row = kv.open("batch")
            if not kv.ensure(row, nb * kv.block_tokens):
                kv.close(row)
                raise RuntimeError(f"warm-up: no room for {nb} blocks")
            kv.preempt(row)
            kv.restore(row)
            kv.close(row)
            swaps += 1
    import jax
    jax.block_until_ready(eng.pool.storage)
    return {"prompt_lengths": lens, "swap_shapes": swaps}


# -- the measured window ----------------------------------------------------

class Window:
    """Drives the cell's traffic through ``Engine.submit`` / ``poll`` and
    stamps every token when the poll that made it returns."""

    def __init__(self, eng, cell: manifest.Cell, seed: int, seconds: float,
                 trace_pages: bool):
        self.eng, self.cell, self.seconds = eng, cell, seconds
        self.trace_pages = trace_pages
        self.rng = np.random.default_rng(seed_key(seed).spawn(1)[0])
        self.vocab = cell.config["published"]["vocab_size"]
        self.reqs: list[Req] = []
        self.inflight: list[Req] = []
        self.sessions: dict[str, dict] = {}    # sid -> prompt, reqs, tier
        self.steps: list[dict] = []
        self.prefills: list[int] = []          # prompt lengths, in window
        self.polls: list[tuple[float, float]] = []
        self.waiting: list[int] = []           # queue length after a poll
        self.swapped: set[str] = set()
        self.t0 = 0.0
        self.closed = False
        self.n_sessions = 0
        mix = cell.mix
        if mix["loop"] == "open":
            rate = float(cell.spec["sessions_per_s"])
            self.pending = gen.open_loop(mix, rate, seconds, self.vocab,
                                         self.rng)
            self.pending.sort(key=lambda s: s.arrival_s)
            self.turns = {s.sid: list(s.turns) for s in self.pending}
            self.loop = None
            self.prompt_lengths = sorted({len(s.prompt)
                                          for s in self.pending})
        else:
            if "sessions" in mix:
                self.n_sessions = sessions_for(eng, mix,
                                               cell.config["max_len"])
            self.loop = gen.ClosedLoop(mix, self.vocab, self.rng,
                                       cell.config["max_len"],
                                       self.n_sessions)
            self.pending = []
            self.prompt_lengths = gen.support(mix["prompt"])

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _submit(self, sid, tier, prompt, max_new, sched, fresh):
        from repro.serve import ServeRequest
        if fresh:
            self.sessions[sid] = {"prompt": prompt, "reqs": [], "tier": tier}
        r = Req(sid, tier, sched)
        r.obj = ServeRequest(sid, prompt, max_new, tier=tier)
        self.eng.submit(r.obj)
        r.submit = self.now()
        self.sessions[sid]["reqs"].append(r)
        self.reqs.append(r)
        self.inflight.append(r)
        if fresh and not self.closed:
            self.prefills.append(len(prompt))

    def _next_closed(self, sched: float) -> None:
        busy = {r.sid for r in self.inflight}
        sid, tier, prompt, m, fresh = self.loop.next(busy)
        for old in self.loop.retired:
            close(self.eng, old)
        self.loop.retired.clear()
        self._submit(sid, tier, prompt, m, sched, fresh)

    def _arrivals(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.submit"):
            t = self.now()
            while self.pending and self.pending[0].arrival_s <= t:
                s = self.pending.pop(0)
                self._submit(s.sid, s.tier, s.prompt,
                             self.turns[s.sid].pop(0), s.arrival_s, True)

    def _finished(self, r: Req, stamp: float) -> None:
        """A request completed at ``stamp``: queue what follows it. After
        the window nothing follows, and the session is closed."""
        if self.closed:
            if r.sid != FLIP_SID:
                close(self.eng, r.sid)
            return
        if self.loop is None:
            left = self.turns[r.sid]
            if left:
                self._submit(r.sid, r.tier, self.sessions[r.sid]["prompt"],
                             left.pop(0), stamp, False)
            else:
                close(self.eng, r.sid)
        else:
            if not self.loop.n_sessions:
                close(self.eng, r.sid)
            self._next_closed(stamp)

    def poll(self) -> None:
        import jax
        eng = self.eng
        steps0 = eng.steps
        t_start = self.now()
        with jax.profiler.TraceAnnotation("bench.poll"):
            done = eng.poll()
        t_end = self.now()
        self.polls.append((t_start, t_end))
        self.waiting.append(len(eng.sched.waiting))
        if eng.steps > steps0 and not self.closed:
            self._record_step(done)
        for r in self.inflight:
            n = len(r.obj.generated)
            if n > r.seen:
                if r.seen == 0:
                    r.first_poll = t_start
                r.stamps.extend([t_end] * (n - r.seen))
                r.seen = n
        ids = {id(d) for d in done}
        fin = [r for r in self.inflight if id(r.obj) in ids]
        for r in fin:
            r.done = True
        self.inflight = [r for r in self.inflight if not r.done]
        if self.loop is not None and self.loop.n_sessions:
            self._track_swaps()
        for r in fin:
            self._finished(r, t_end)

    def _record_step(self, done) -> None:
        eng = self.eng
        stepped = [s for s in eng.sched.slots if s is not None]
        stepped += [eng.sched.sessions[d.seq_id] for d in done
                    if d.seq_id in eng.sched.sessions]
        lens = [s.cache_len - 1 for s in stepped]
        rec = {"lens": lens}
        if self.trace_pages:
            rec["secded_pages"] = secded_live_pages(eng, stepped)
        self.steps.append(rec)

    def _track_swaps(self) -> None:
        kv = self.eng.kv
        for sid, s in self.eng.sched.sessions.items():
            if s.slot is None and not kv.resident(s.row):
                self.swapped.add(sid)

    def run(self) -> None:
        import jax
        self.swap0 = swap_counters(self.eng)
        self.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            if self.loop is not None:
                for _ in range(self.cell.mix["clients"]):
                    self._next_closed(0.0)
            while self.now() < self.seconds:
                if self.loop is None:
                    self._arrivals()
                if self.eng.sched.has_work():
                    self.poll()
                else:       # idle until the next arrival
                    nxt = self.pending[0].arrival_s if self.pending \
                        else self.seconds
                    time.sleep(max(0.0, min(nxt - self.now(), 0.01)))
            self.closed = True
            self.t_close = self.now()
        self._close_idle()
        self.swap1 = swap_counters(self.eng)

    def _close_idle(self) -> None:
        """Close every session that is parked with no request queued: the
        window is over and no turn follows. The check's paid turn then
        finds the paid tier's frames free, and never waits on the host
        swap tier, which a cell that bypasses it does not exercise."""
        sched = self.eng.sched
        queued = {q.seq_id for q in sched.waiting}
        for sid, s in list(sched.sessions.items()):
            if s.slot is None and sid not in queued and sid != FLIP_SID:
                close(self.eng, sid)

    def drain(self) -> int:
        """Open loop: poll on until every request due in the window has
        its first token (at most :data:`DRAIN_S`); the wait counts in its
        time to first token. Returns the requests that never got one."""
        if self.loop is not None:
            return 0
        end = self.t_close + DRAIN_S
        while any(not r.stamps for r in self.reqs) and self.now() < end \
                and self.eng.sched.has_work():
            self.poll()
        return sum(1 for r in self.reqs if not r.stamps)


def sessions_for(eng, mix: dict, max_len: int) -> int:
    """N sessions whose KV at their mean context is ``working_set`` times
    the pool's device capacity (counted by the VM), never fewer than two
    per client so that a caller always finds one not in flight."""
    kv = eng.kv
    cap = eng.vm.device_capacity_pages(eng.pool_name)
    ctx = gen.mean_context(mix, max_len)
    per = kv.blocks_for(math.ceil(ctx)) * kv.n_layers
    n = math.ceil(mix["sessions"]["working_set"] * cap / per)
    return max(n, 2 * mix["clients"])


def secded_live_pages(eng, sessions) -> int:
    """Live pages of these sessions that sit in the SECDED rows."""
    pool, kv = eng.pool, eng.kv
    if not hasattr(pool, "boundary") or getattr(pool, "num_shards", 1) > 1:
        return 0
    rows = np.asarray([s.row for s in sessions], np.int64)
    if not len(rows):
        return 0
    phys = kv.gather_phys(rows)                  # (n, L, maxB)
    nb = np.asarray([kv.blocks_for(s.cache_len) for s in sessions])
    live = np.arange(kv.max_blocks)[None, None, :] < nb[:, None, None]
    sec = (phys >= pool.boundary) & (phys < pool.num_rows)
    return int((sec & live).sum())


# -- the check --------------------------------------------------------------

def plant_flip(win: Window) -> dict:
    """After the window, through the same compiled programs: open a paid
    (SECDED) session with the mix's longest prompt and decode a few
    tokens, flip :data:`FLIP_BITS` of one V word of its layer-0, block-0
    page in storage, then decode :data:`FLIP_TOKENS` more. SECDED must
    correct the bit on every read; the session is compared with the
    reference like the sample."""
    import jax.numpy as jnp
    eng = win.eng
    pool = eng.pool
    if getattr(pool, "num_shards", 1) > 1 or not hasattr(pool, "boundary"):
        return {}
    p = max(win.prompt_lengths)
    prompt = gen.prompt_tokens(win.rng, p, win.vocab)
    sid = FLIP_SID
    for fresh in (True, False):
        win._submit(sid, "paid", prompt, FLIP_TOKENS, win.now(), fresh)
        r = win.reqs[-1]
        while not r.done:
            win.poll()
        if not fresh:
            break
        s = eng.sched.sessions[sid]
        pool = eng.pool
        page = int(eng.kv.gather_phys(np.asarray([s.row]))[0, 0, 0])
        if not pool.boundary <= page < pool.num_rows:
            raise RuntimeError(f"paid session's page {page} is not in a "
                               "SECDED row")
        # V of token 0, head 0, dim 5: V is used linearly, so a value
        # blown up by an exponent bit shows in every later token
        at = eng.kv.kv_words // 2 + 5
        lane, word = divmod(at, pool.row_words)
        mask = sum(1 << b for b in FLIP_BITS)
        st = pool.storage
        st = st.at[page, lane, word].set(st[page, lane, word]
                                         ^ jnp.uint32(mask))
        eng.vm.pools[eng.pool_name] = dataclasses.replace(pool, storage=st)
    return {"session": sid, "page": page, "lane": lane, "word": word,
            "bits": list(FLIP_BITS)}


def pick_sample(win: Window, rng: np.random.Generator,
                must: list[str]) -> list[str]:
    """Sessions with a finished request: the longest, ``must``, and a
    draw from the seed of up to :data:`SAMPLE` more."""
    fin = sorted({r.sid for r in win.reqs if r.done})
    if not fin:
        return []

    def length(sid):
        return sum(len(r.obj.generated) for r in win.sessions[sid]["reqs"])

    longest = max(fin, key=length)
    rest = [s for s in fin if s != longest and s not in must]
    drawn = list(rng.choice(rest, size=min(SAMPLE, len(rest)),
                            replace=False)) if rest else []
    return list(dict.fromkeys([longest, *[m for m in must if m in fin],
                               *drawn]))


def streams(win: Window, sids: list[str]) -> list[tuple[np.ndarray, int]]:
    """(tokens: prompt then every served token, prompt length) per
    session."""
    out = []
    for sid in sids:
        s = win.sessions[sid]
        served = [t for r in s["reqs"] for t in r.obj.generated]
        out.append((np.concatenate([s["prompt"],
                                    np.asarray(served, np.int32)]),
                    len(s["prompt"])))
    return out


def gap_fn(ref, published: dict, control: bool):
    """Jitted ``(weights, tokens (S,)) -> per position: reference best
    logit, reference logit of the next token, and (with ``control``) the
    reference logit of the token the int8 (W8A8) control puts first."""
    import jax
    import jax.numpy as jnp
    f32 = ref.logits_fn(published)
    bf = ref.logits_fn(published, w8a8=True) if control else None

    @jax.jit
    def f(w, toks):
        lg = f32(w, toks)
        best = lg.max(axis=-1)
        nxt = jnp.take_along_axis(lg[:-1], toks[1:, None], axis=1)[:, 0]
        out = {"best": best[:-1], "next": nxt}
        if bf is not None:
            pick = jnp.argmax(bf(w, toks), axis=-1)
            out["control"] = jnp.take_along_axis(lg, pick[:, None],
                                                 axis=1)[:-1, 0]
        return out

    return f


def reference_gaps(weights, cell: manifest.Cell, seqs, control: bool = False
                   ) -> dict:
    """Gaps by which served tokens' reference logits lie below the
    reference's best, over every served token of ``seqs``: the widest
    (``logit_gap``) and the mean (``mean_gap``); with ``control``, the
    same two for the token the int8 control puts first at each of those
    positions."""
    import jax.numpy as jnp
    ref = manifest.reference(cell.config)
    f = gap_fn(ref, cell.config["published"], control)
    S = cell.config["max_len"] + 1     # a stream ends with one token unfed
    gaps, cgaps = [], []
    for toks, p in seqs:
        n = len(toks)
        pad = np.zeros(S, np.int32)
        pad[:n] = toks
        out = {k: np.asarray(v, np.float64)
               for k, v in f(weights, jnp.asarray(pad)).items()}
        sl = slice(p - 1, n - 1)        # positions that predicted a token
        gaps.append(out["best"][sl] - out["next"][sl])
        if control:
            cgaps.append(out["best"][sl] - out["control"][sl])
    res = {"tokens": int(sum(g.size for g in gaps))}
    res["logit_gap"], res["mean_gap"] = _widest_and_mean(gaps)
    if control:
        res["control_gap"], res["control_mean_gap"] = _widest_and_mean(cgaps)
    return res


def _widest_and_mean(gaps: list) -> tuple[float, float]:
    """Widest and mean of the gaps; a NaN or an infinity reads as the
    largest finite number JSON carries."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not np.isfinite(g).all():
        return 1e30, 1e30
    return (float(g.max()), float(g.mean())) if g.size else (0.0, 0.0)


# -- the whole run ----------------------------------------------------------

def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, root: str = ROOT,
             data: str = HERE, control: bool = False,
             spec: dict | None = None) -> dict:
    """Everything of one run; returns the result line as a dict.
    ``control`` also reads the int8 control's gap on the same sample,
    and the gap with one served token altered (the planted fault);
    ``spec`` overrides keys of the cell file (the knee sweep's rate)."""
    cell = manifest.load_cell(name, root, data)
    cell.spec.update(spec or {})
    devices = check_devices(cell.chips, require_tpu)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import memprof, metrics, tracing
    metrics.disable()
    tracing.disable()
    memprof.disable()
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    cfg = program_config(cell)
    eng = build_engine(cell, cfg, devices)
    weights = install_weights(eng, cell, seed)
    if devices[0].platform == "tpu":
        check_pallas_gather(eng)
    win = Window(eng, cell, seed, seconds, trace_pages=trace)
    warm = warm_up(eng, cell, win.prompt_lengths)
    compiles = CompileCounter()
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with compiles:
        win.run()
    if trace:
        jax.profiler.stop_trace()
    failed = win.drain()
    flip = plant_flip(win)
    peak = memory_peak(devices)
    rng = np.random.default_rng(seed_key(seed).spawn(2)[1])
    must = [flip["session"]] if flip else []
    must += sorted(win.swapped)[:4]
    sids = pick_sample(win, rng, must)
    seqs = streams(win, sids)
    run = RunRecord(cell, win, setup_s, devices, compiles.n,
                    n_sessions=win.n_sessions)
    tr = None
    if trace:
        import trace_reduce
        tr = trace_reduce.reduce_file(trace_reduce.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        run.trace = tr
    eng.vm.pools.clear()
    del eng, win
    gc.collect()
    gaps = reference_gaps(weights, cell, seqs, control=control)
    if control and seqs:
        # the planted fault: the same sample with one served token altered
        toks, p = seqs[0]
        bad = toks.copy()
        bad[-1] = (bad[-1] + 1) % cell.config["published"]["vocab_size"]
        fault = reference_gaps(weights, cell, [(bad, p), *seqs[1:]])
        gaps["fault_gap"] = fault["logit_gap"]
        gaps["fault_mean_gap"] = fault["mean_gap"]
    limits = cell.spec["limits"]
    checks = {"mean_gap": {"value": gaps["mean_gap"],
                           "limit": limits["mean_gap"]},
              "tokens_compared": {"value": gaps["tokens"],
                                  "limit": limits["min_tokens"]}}
    correct = (gaps["mean_gap"] <= limits["mean_gap"]
               and gaps["tokens"] >= limits["min_tokens"])
    out_metrics = {}
    for m in cell.metrics(trace):
        v = manifest.reader(m["name"])(run)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(run.requests),
              "failed": int(failed), "metrics": out_metrics,
              "device": device}
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        import trace_reduce
        result["breakdown"] = {"device_ops": trace_reduce.top(tr["ops"]),
                               "idle_gaps": trace_reduce.top(
                                   tr["idle_gaps"])}
    info = {"seed": seed, "logit_gap": gaps["logit_gap"],
            "warm_up": warm, "compiles_in_window": compiles.n,
            "flip": flip, "sampled_sessions": len(sids),
            "swapped_sessions": len(run.swapped),
            "n_sessions": run.n_sessions, "steps": len(run.steps),
            "polls": len(run.polls)}
    if control:
        for k in ("logit_gap", "control_gap", "control_mean_gap",
                  "fault_gap", "fault_mean_gap"):
            info[k] = result[k] = gaps.get(k)
        w, k = run.waiting, len(run.waiting) // 3
        thirds = [float(np.mean(w[i * k:(i + 1) * k])) for i in range(3)] \
            if k else w
        info["waiting_thirds"] = result["waiting_thirds"] = thirds
    if trace:
        info["modules"] = trace_reduce.top(tr["modules"], 16)
    log(f"chipbench info {info}")
    result["checks"] = checks
    return result


class CompileCounter:
    """Counts XLA compilations (backend compiles, cache misses and cache
    loads alike) while active, through ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.n = 0
        self.on = False
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name in self.EVENTS:
            self.n += 1

    def __enter__(self):
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False


class RunRecord:
    """What the metric readers read: the window's requests, polls and
    decode steps on the host clock, the engine's counters, the shapes, the
    peaks and (with ``--trace 1``) the reduced device trace."""

    def __init__(self, cell, win: Window, setup_s: float, devices,
                 compiles: int, n_sessions: int):
        import counts
        ref = manifest.reference(cell.config)
        eng = win.eng
        self.cell = cell.name
        self.loop = cell.mix["loop"]
        self.chips = len(devices)
        self.setup_s = setup_s
        self.window_s = win.t_close
        # requests due in the window (not the check's own turn after it)
        self.requests = [r for r in win.reqs if r.sched <= win.t_close]
        self.polls = win.polls
        self.waiting = win.waiting
        self.last_poll_end = win.polls[-1][1] if win.polls else 0.0
        self.steps = win.steps
        self.prefills = win.prefills
        self.max_batch = eng.max_batch
        self.compiles = compiles
        self.n_sessions = n_sessions
        self.swapped = set(win.swapped)
        self.dims = ref.dims(cell.config["published"])
        self.counts = counts
        self.block_tokens = eng.kv.block_tokens
        self.page_bytes = 4 * eng.kv.page_words
        self.code_bytes = 4 * cell.config["pool"]["row_words"]
        self.swap_pages = {k: win.swap1[k] - win.swap0[k] for k in win.swap0}
        self.peaks = manifest.peaks(devices[0].device_kind) \
            if devices[0].platform == "tpu" else None
        self.layers = manifest.opnames()
        self.trace = None

    def layer_s(self, layer: str) -> float:
        """Device seconds of one layer's programs in the traced window.
        A layer that no traced program matches is an error, not 0: its
        names in ``opnames.json`` no longer fit the compiled programs."""
        import trace_reduce
        sec = trace_reduce.layer_seconds(self.trace["modules"],
                                         self.layers)[layer]
        if not sec:
            raise RuntimeError(f"no program in the trace matches layer "
                               f"{layer!r} ({self.layers[layer]}); traced: "
                               f"{sorted(self.trace['modules'])}")
        return sec


def swap_counters(eng) -> dict:
    """Pages swapped out to the host tier (each takes a new host slot)
    and pages read back from it (the VM's page faults)."""
    return {"out": eng.vm._next_slot, "in": eng.vm.stats.host_reads}
