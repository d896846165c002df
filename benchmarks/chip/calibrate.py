#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, and the chat cell's knee.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds <a,b,...> --seconds <s> [--rates <r1,r2,...>]

Runs the cell once per seed (or, with ``--rates``, once per arrival rate
on the first seed) in ONE process, each a whole benchmark run with the
check extended: beside the program's widest and mean logit gaps it reads
the int8 control's at the same positions (the reference put in the
program's place one precision below the configuration's bfloat16
products) and the gaps with one served token altered (the planted fault).
One JSON line per run on standard output. The benchmark's own runs
never do this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    import harness
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = [(seeds[0], {"sessions_per_s": float(r)})
            for r in args.rates.split(",") if r] or [(s, {}) for s in seeds]
    for seed, spec in runs:
        t = time.perf_counter()
        r = harness.run_cell(args.workload, seed, args.seconds, False, t,
                             control=True, spec=spec)
        print(json.dumps({"seed": seed, **spec, "correct": r["correct"],
                          "mean_gap": r["checks"]["mean_gap"]["value"],
                          **{k: r[k] for k in (
                              "logit_gap", "control_gap", "control_mean_gap",
                              "fault_gap", "fault_mean_gap")},
                          "tokens": r["checks"]["tokens_compared"]["value"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "metrics": {k: v["value"] for k, v in
                                      r["metrics"].items()},
                          "device": r["device"],
                          "waiting_thirds": r.get("waiting_thirds"),
                          "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
