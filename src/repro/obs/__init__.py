"""CREAM-Scope — the unified telemetry plane.

Three cooperating pieces, all host-side control plane (nothing here ever
runs inside jit; device-side accumulators are tiny status arrays produced
by the existing fused reads and *folded* into the registry between steps):

  * :mod:`repro.obs.metrics` — a process-global registry of counters /
    gauges / histograms with labelled series (pool, reliability class,
    tier, region), a Prometheus-style text exposition, and fold helpers
    for device-side status accumulators;
  * :mod:`repro.obs.tracing` — nestable spans, each also a
    ``jax.profiler`` annotation on the device trace's clock, with a
    Perfetto / chrome-tracing JSON exporter, instrumenting the named hot
    paths (``Engine.poll`` and its admission, prefill and decode-step
    phases, the shard dispatch and ``ppermute`` migration ring,
    ``repartition_with_migration``, scrub sweeps, objcache batched
    get/set);
  * :mod:`repro.obs.slo` + :mod:`repro.obs.dashboard` — per-reliability-
    class SLO tracking (uncorrectable reads on SECDED frames must be 0;
    capacity reclaimed rides the boundary register) and a terminal
    snapshot dashboard (``tools/creamtop.py``);
  * :mod:`repro.obs.memprof` — CREAM-Lens, the bank-level memory-system
    profiler: captures the data plane's page-access streams, attributes
    them to (chip, bank, row) via the layout translation, and replays
    them through the per-bank state machines in ``benchmarks/dram_sim``
    (row-buffer hits/conflicts, achieved BLP, tRRD/tFAW stalls).

Everything is opt-in: with all planes disabled (the default) every
instrumentation site reduces to one boolean check, so the hot paths stay
one-gather/one-scatter with no extra dispatches.
"""
from repro.obs import dashboard, memprof, metrics, slo, tracing

__all__ = ["metrics", "tracing", "slo", "dashboard", "memprof"]
