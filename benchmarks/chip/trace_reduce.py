"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the file with ``jax.profiler.ProfileData`` alone. Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation and the ``XLA Modules`` line one per compiled program run. The
traced window is the host annotation :data:`WINDOW` that the benchmark
places around its measured window; every device interval is clipped to it.

Gives, averaged over the chips traced:

* ``busy_s``: the union of operation intervals (``idle = window - busy``);
* ``modules``: device seconds per compiled program, by name;
* ``ops``: device seconds per operation name;
* ``collective_exposed_s``: collective operations' time with no other
  operation running on that chip;
* ``idle_gaps``: device idle time split by what the host was doing, by
  the benchmark's own annotations (``bench.*``) covering each part of a
  gap (``bench.none``: outside any, the benchmark loop waiting).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"psum|ppermute", re.I)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def _overlap(a: tuple[float, float], spans) -> dict[str, float]:
    got: dict[str, float] = {}
    for name, s, e in spans:
        o = min(a[1], e) - max(a[0], s)
        if o > 0:
            got[name] = got.get(name, 0.0) + o
    return got


def reduce_profile(pd) -> dict:
    """Reduce a loaded ``ProfileData``; times in seconds."""
    host_spans: list[tuple[str, float, float]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith("bench."):
                    host_spans.append((ev.name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("trace has no /device:TPU:<n> plane")
    lo, hi = window
    busy = coll = 0.0
    modules: dict[str, float] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for plane in devices:
        op_iv, coll_iv = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.end_ns, lo, hi)
                    if c:
                        n = _module_name(ev.name)
                        modules[n] = modules.get(n, 0.0) + c[1] - c[0]
            elif line.name == "XLA Ops":
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.end_ns, lo, hi)
                    if not c:
                        continue
                    ops[ev.name] = ops.get(ev.name, 0.0) + c[1] - c[0]
                    (coll_iv if COLLECTIVE.search(ev.name)
                     else op_iv).append(c)
        comp = _union(op_iv)
        allu = _union(op_iv + coll_iv)
        busy += sum(e - s for s, e in allu)
        coll += sum(e - s for s, e in _union(coll_iv)) - _covered(
            _union(coll_iv), comp)
        prev = lo
        for s, e in allu + [(hi, hi)]:
            if s > prev:
                by = _overlap((prev, s), host_spans)
                by["bench.none"] = s - prev - sum(by.values())
                for name, t in by.items():
                    gaps[name] = gaps.get(name, 0.0) + t
            prev = max(prev, e)
    n = len(devices)
    ns = 1e-9
    return {
        "chips": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n,
        "collective_exposed_s": coll * ns / n,
        "modules": {k: v * ns / n for k, v in modules.items()},
        "ops": {k: v * ns / n for k, v in ops.items()},
        "idle_gaps": {k: v * ns / n for k, v in gaps.items()},
    }


def _covered(a: list[tuple[float, float]], b: list[tuple[float, float]]
             ) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def layer_seconds(modules: dict[str, float], patterns: dict[str, list[str]]
                  ) -> dict[str, float]:
    """Device seconds per layer: a program counts for the first layer one
    of whose regular expressions it matches."""
    out = {k: 0.0 for k in patterns}
    for name, sec in modules.items():
        for layer, pats in patterns.items():
            if any(re.search(p, name) for p in pats):
                out[layer] += sec
                break
    return out
