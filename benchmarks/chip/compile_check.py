#!/usr/bin/env python3
"""Compile each config's step programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py [config ...]

For each configuration file under ``configs/`` (or those named), compiles
at the cell's sizes, for one chip of a described ``v5e:2x2``, the programs
a decode step and a prefill run: the page gather (the Pallas mixed read),
the model step (attend), the page scatter and the prefill of the longest
prompt. Prints each program's ``memory_analysis()`` bytes and a reckoning
of the fullest moment: weights + pool + the largest program's arguments
not already counted, its output and temporaries. The engine is built with
weights as shapes only (nothing of model size is allocated here).
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, f"{k}_size_in_bytes"))
            for k in ("argument", "output", "temp", "alias")}


def check(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import harness
    import manifest
    from repro.core import pool as pool_lib
    from repro.serve import engine as engine_mod
    from traffic import gen

    c = json.load(open(os.path.join(HERE, "configs", f"{name}.json")))
    cell = manifest.Cell(name, {"chips": 1}, c, {}, [], [])
    cfg = harness.program_config(cell)
    dev = SingleDeviceSharding(topo.devices[0])
    real_build = engine_mod.build_model

    def shapes_only(cfg_):
        model = real_build(cfg_)
        return dataclasses.replace(model, init=lambda key: jax.eval_shape(
            model.init, key))

    engine_mod.build_model = shapes_only
    try:
        eng = harness.build_engine(cell, cfg, jax.devices())
    finally:
        engine_mod.build_model = real_build

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)

    params = jax.tree.map(sds, eng.params)
    pool = eng.pool
    storage = sds(pool.storage)
    B, L, maxB = eng.max_batch, eng.n_layers, eng.kv.max_blocks
    n = B * L * maxB
    pw = eng.kv.page_words
    i32 = jnp.int32
    out = {}
    gather = eng._mixed_read.lower(
        storage, jax.ShapeDtypeStruct((n,), i32, sharding=dev),
        layout=pool.layout, num_rows=pool.num_rows, boundary=pool.boundary,
        use_kernel=True)
    gather = gather.compile()
    out["gather_has_pallas_call"] = "tpu_custom_call" in gather.as_text()
    out["gather"] = _mem(gather)
    pages = jax.ShapeDtypeStruct((n, pw), jnp.uint32, sharding=dev)
    vec = jax.ShapeDtypeStruct((B,), i32, sharding=dev)
    out["attend"] = _mem(eng._attend.lower(params, pages, vec, vec)
                         .compile())
    st = dataclasses.replace(pool, storage=storage)
    ids = jax.ShapeDtypeStruct((B * L,), i32, sharding=dev)
    data = jax.ShapeDtypeStruct((B * L, pw), jnp.uint32, sharding=dev)
    out["scatter"] = _mem(pool_lib._write_pages_any_jitted.lower(
        st, ids, data).compile())
    cells = [json.load(open(os.path.join(HERE, "workloads", f)))
             for f in sorted(os.listdir(os.path.join(HERE, "workloads")))]
    mixes = [json.load(open(os.path.join(HERE, "traffic",
                                         f"{w['traffic']}.json")))
             for w in cells if w["config"] == name]
    longest = max(max(gen.support(m["prompt"])) for m in mixes)
    toks = jax.ShapeDtypeStruct((1, longest), i32, sharding=dev)
    out["prefill"] = _mem(eng._prefill.lower(params, toks).compile())
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pbytes = storage.size * storage.dtype.itemsize
    out["weights_bytes"] = int(wbytes)
    out["pool_bytes"] = int(pbytes)
    worst = 0
    for prog in ("gather", "attend", "scatter", "prefill"):
        m = out[prog]
        resident = wbytes + pbytes
        extra = max(m["argument"] - resident, 0) + m["output"] + m["temp"]
        worst = max(worst, resident + extra)
    out["fullest_bytes"] = int(worst)
    out["prefill_prompt"] = longest
    return out


def main(argv: list[str]) -> int:
    import jax
    from jax.experimental import topologies

    from repro.kernels.mixed import kernel as mixed_kernel
    # the host is a CPU: steer the kernel off interpret mode, and keep
    # described-chip compiles out of the persistent cache (no chip can
    # read them back)
    mixed_kernel.use_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or sorted(f[:-5] for f in os.listdir(
        os.path.join(HERE, "configs")) if f.endswith(".json"))
    for name in names:
        print(json.dumps({"config": name, **check(name, topo)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
