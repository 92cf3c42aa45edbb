"""Paper Fig. 12 — GEMM + Reduce-Scatter: fused/overlapped vs unfused.

Runs in a CPU-only subprocess with 8 host-platform devices (so the main
process and other benches keep their own devices, and the child never
competes for a chip the parent holds). Rows are CPU timings. Compares:
  * unfused — the collective_matmul program's psum_scatter variant
    (full local GEMM then reduce-scatter, the cuBLAS+NCCL analogue)
  * fused   — the ring variant of the same program stage
    (collective_matmul/kshard — one tune key, two schedules)
and reports wall-time plus the layout-inferred collective plan bytes.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import row

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import collective as coll
from repro.core.dtensor import DTensorSpec
from repro.kernels import programs

mesh = compat.make_mesh((8,), ("model",))
M, K, N = 1024, 2048, 1024
a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)

def run(mode):
    def body(a, b):
        return programs.collective_matmul(
            a, b, axis_name="model",
            impl="ring" if mode == "fused" else "psum_scatter")
    f = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(P(None, "model"), P("model", None)),
                out_specs=P("model", None), check_vma=False))
    out = f(a, b); jax.block_until_ready(out)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); jax.block_until_ready(f(a, b))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts)//2] * 1e6, out

us_u, out_u = run("unfused")
us_f, out_f = run("fused")
err = float(jnp.max(jnp.abs(out_u - out_f)))

# layout-pair collective inference (Fig. 8): partial sums over model ->
# dst shards dim0 on model => ReduceScatter
ms = {"model": 8}
src = DTensorSpec.from_pspec((M, N), (None, None), ms)
dst = DTensorSpec.from_pspec((M, N), ("model", None), ms)
plan = coll.infer_redistribution(src, dst, ms, partial_axes=("model",))
pbytes = coll.plan_comm_bytes(plan, src, ms, 4)
print(json.dumps({"us_unfused": us_u, "us_fused": us_f, "err": err,
                  "plan": [type(s).__name__ for s in plan], "plan_bytes": pbytes}))
"""


def run() -> list:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.dirname(os.path.dirname(__file__))] + sys.path
    )
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True, env=env
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"gemm_rs child failed (exit {out.returncode}):\n{out.stderr[-2000:]}"
        )
    data = json.loads(out.stdout.strip().splitlines()[-1])
    return [
        row("gemm_rs.unfused", data["us_unfused"],
            "cpu 8 host devices; full GEMM + psum_scatter"),
        row("gemm_rs.fused", data["us_fused"],
            f"cpu 8 host devices; ring overlap; err={data['err']:.1e}; "
            f"plan={'+'.join(data['plan'])}; plan_bytes={data['plan_bytes']}"),
    ]
