#!/usr/bin/env python3
"""Ahead-of-time rehearsal of a cell, with no chip attached: compile its
weight init, its decode step and its prefill buckets at full size for a
described TPU v5e, and print each program's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot.py <cell> [<cell> ...]

Nothing runs, so this gives sizes and refusals, never a time.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"argument {m.argument_size_in_bytes} B, output {m.output_size_in_bytes} B, "
            f"temporary {m.temp_size_in_bytes} B, alias {m.alias_size_in_bytes} B")


def rehearse(name: str, dev) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import cells
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, name)
    doc, mix = cells.load_config(cell.config), cells.load_traffic(cell.traffic)
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    one = jax.sharding.SingleDeviceSharding(dev)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    def make_weights(k):
        return ref.init_params(sz, k)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    t = time.perf_counter()
    c = jax.jit(make_weights, out_shardings=one).lower(key).compile()
    print(f"{name} weights init: {_mem(c)} ({time.perf_counter() - t:.1f} s)", flush=True)
    params = on_chip(jax.eval_shape(make_weights, jax.random.PRNGKey(0)))
    cfg = dataclasses.replace(get_config(doc["program"]["arch"]), **doc["program"]["set"])
    api = build_model(cfg)
    slots, max_seq = int(mix["slots"]), int(mix["max_seq"])
    eng = ServeEngine(api, batch_size=slots, max_seq=max_seq)
    cache = on_chip(jax.eval_shape(lambda: api.cache_init(slots, max_seq)))
    print(f"{name} cache: {sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))} B",
          flush=True)
    tok = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    t = time.perf_counter()
    c = eng.decode_fn().lower(params, cache, tok, tok).compile()
    text = c.as_text()
    print(f"{name} decode step ({slots} slots): {_mem(c)}; "
          f"{text.count('custom_call_target=\"tpu_custom_call\"')} kernels "
          f"({time.perf_counter() - t:.1f} s)", flush=True)
    one_cache = on_chip(jax.eval_shape(lambda: api.cache_init(1, max_seq)))
    for s in mix["prompt"]["buckets"]:
        toks = jax.ShapeDtypeStruct((1, int(s)), jnp.int32, sharding=one)
        t = time.perf_counter()
        c = jax.jit(api.prefill).lower(params, {"tokens": toks}, one_cache).compile()
        print(f"{name} prefill {s}: {_mem(c)} ({time.perf_counter() - t:.1f} s)", flush=True)


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from repro.tune import cache as tune_cache

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # resolve schedules and the interpret flag as on the chip
    jax.default_backend = lambda: "tpu"
    tune_cache._default = tune_cache.ScheduleCache(None)
    for name in argv:
        rehearse(name, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
