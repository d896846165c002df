"""Benchmark harness: one entry per paper table/figure + beyond-paper runs.

Prints ``name,us_per_call,derived`` CSV (scaffold contract). Figure map:
  fig4_*   WebSearch latency vs capacity        (paper Fig. 4)
  fig8_*   memcached speedups                   (paper Fig. 8)
  fig9_*   multiprogrammed weighted speedup     (paper Figs. 9, 10a/b, 11a/b)
  fig12_*  SECDED-fraction sensitivity vs SoftECC (paper Fig. 12)
  ops_* / kernel_*  layout + kernel overheads   (paper §4.4 analogue)
  serving_*         CREAM-Serve paged-KV engine — the real Fig. 8 serving
                    analogue (CREAM vs SECDED throughput + p50/p99)
  vm_*              CREAM-VM multi-tenant sim   (beyond paper)
  objcache_*        CREAM-Cache real-data-plane memcached (beyond paper)
  fig9_real_*       CREAM-Shard measured bank parallelism (shard suite)

``--only NAME[,NAME...]`` runs a subset of suites (CI smoke uses
``--only vm,kernels,objcache,shard``). ``--json [DIR]`` additionally writes
one machine-readable ``BENCH_<suite>.json`` per suite
(``{name: us_per_call}``), flushed *as each suite finishes* — a suite that
fails later never discards the files (or rows) already earned; a failing
suite's partial rows land in ``BENCH_<suite>.partial.json`` so the
trajectory survives without poisoning the regression gate
(``benchmarks/check_regression.py`` reads only the non-partial files).
``--seed N`` is forwarded to every suite whose entry point accepts a
``seed`` keyword. ``--memprof`` attaches CREAM-Lens
(:mod:`repro.obs.memprof`): each suite's captured page-access streams are
replayed through the per-bank DRAM state machines and the resulting bank
profile is embedded as ``_memprof`` + written to ``MEMPROF_<suite>.json``.
"""
import argparse
import inspect
import json
import os
import sys
import time
import traceback

# self-bootstrap: `python benchmarks/run.py` puts benchmarks/ (not the repo
# root) on sys.path, so `from benchmarks import ...` needs this
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main() -> None:
    from benchmarks import (bench_capacity, bench_faults, bench_kernels,
                            bench_objcache, bench_overheads,
                            bench_parallelism, bench_sensitivity,
                            bench_serving, bench_shard, bench_vm,
                            bench_websearch)
    suites = [
        ("fig4", bench_websearch.main),
        ("fig8", bench_capacity.main),
        ("fig9-11", bench_parallelism.main),
        ("fig12", bench_sensitivity.main),
        ("overheads", bench_overheads.main),
        ("kernels", bench_kernels.main),
        ("serving", bench_serving.main),
        ("vm", bench_vm.main),
        ("objcache", bench_objcache.main),
        ("shard", bench_shard.main),
        ("faults", bench_faults.main),
    ]
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names to run")
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="also write BENCH_<suite>.json (name -> us_per_call)"
                         " into DIR (default: current directory)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base RNG seed, forwarded to suites that take one")
    ap.add_argument("--profile", action="store_true",
                    help="attach the CREAM-Scope telemetry plane: embed a "
                         "metrics snapshot (_metrics) into each "
                         "BENCH_<suite>.json and write TRACE_<suite>.json "
                         "(Perfetto) + METRICS_<suite>.prom next to them")
    ap.add_argument("--memprof", action="store_true",
                    help="attach CREAM-Lens: capture the data plane's page-"
                         "access streams, replay them through the per-bank "
                         "DRAM state machines, embed the bank profile "
                         "(_memprof) into each BENCH_<suite>.json, write "
                         "MEMPROF_<suite>.json, and (with --profile) add "
                         "Perfetto counter tracks to TRACE_<suite>.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.profile or args.memprof:
        from repro.obs import metrics as obs_metrics
        from repro.obs import slo as obs_slo
        from repro.obs import tracing as obs_tracing
    if args.profile:
        obs_metrics.enable()
        obs_tracing.enable()
    if args.memprof:
        from repro.obs import memprof as obs_memprof
        obs_memprof.enable()
    if args.only:
        wanted = set(args.only.split(","))
        unknown = wanted - {s for s, _ in suites}
        if unknown:
            raise SystemExit(f"unknown suites: {sorted(unknown)}")
        suites = [(s, fn) for s, fn in suites if s in wanted]
    if args.json is not None:
        os.makedirs(args.json, exist_ok=True)
    failed = 0
    for suite, fn in suites:
        t0 = time.time()
        results = {}
        suite_ok = True
        kwargs = {"seed": args.seed} \
            if "seed" in inspect.signature(fn).parameters else {}
        if args.profile:
            # fresh telemetry per suite: each BENCH json's _metrics blob and
            # TRACE file describe that suite alone
            obs_metrics.reset()
            obs_tracing.reset()
            obs_slo.TRACKER.reset()
        if args.memprof:
            obs_memprof.clear()         # records AND published profiles
        try:
            for name, val, derived in fn(**kwargs):
                print(f"{name},{val:.3f},{derived}", flush=True)
                results[name] = val
        except Exception as e:  # noqa: BLE001
            failed += 1
            suite_ok = False
            print(f"{suite},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        if args.memprof:
            blob = obs_memprof.collect()    # also exports cream_dram_* gauges
            if blob["profiles"] or blob["records"]:
                results["_memprof"] = blob
                outdir = args.json if args.json is not None else "."
                os.makedirs(outdir, exist_ok=True)
                mp_path = os.path.join(outdir, f"MEMPROF_{suite}.json")
                with open(mp_path, "w") as f:
                    json.dump(blob, f, indent=2, sort_keys=True)
                print(f"# wrote MEMPROF_{suite}.json", flush=True)
                if args.profile:
                    # bank-occupancy counter lanes next to the spans
                    obs_tracing.TRACER.extend(obs_memprof.counter_events(blob))
        if args.profile:
            outdir = args.json if args.json is not None else "."
            os.makedirs(outdir, exist_ok=True)
            results["_metrics"] = obs_metrics.collect()
            obs_tracing.export(os.path.join(outdir, f"TRACE_{suite}.json"))
            with open(os.path.join(outdir, f"METRICS_{suite}.prom"),
                      "w") as f:
                f.write(obs_metrics.snapshot())
            print(f"# wrote TRACE_{suite}.json, METRICS_{suite}.prom",
                  flush=True)
        if args.json is not None:
            # flush per suite, immediately: a crash in a later suite (or in
            # this one) must never discard trajectory already earned
            if suite_ok:
                path = os.path.join(args.json, f"BENCH_{suite}.json")
            else:
                # quarantine partial rows under a name the regression gate
                # ignores — a trajectory diff would read a partial suite as
                # a valid (regressed) measurement
                path = os.path.join(args.json, f"BENCH_{suite}.partial.json")
            with open(path, "w") as f:
                json.dump(results, f, indent=2, sort_keys=True)
            print(f"# wrote {path}" + ("" if suite_ok else " (suite failed)"),
                  flush=True)
        print(f"# {suite} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        raise SystemExit(f"{failed} suites failed")


if __name__ == '__main__':
    main()
