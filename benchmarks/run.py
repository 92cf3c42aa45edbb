"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  bench_gemm    — Fig. 10 (GEMM throughput, real weight shapes)
  bench_moe     — Fig. 11 (MoE layer latency vs tokens)
  bench_gemm_rs — Fig. 12 (GEMM+ReduceScatter fused vs unfused, 8-dev)
  bench_mha     — Fig. 13 (MHA across lengths; kernel check)
  bench_layout  — §3.3  (layout-operator trace-time cost)
"""
import sys


def main() -> None:
    from benchmarks import bench_gemm, bench_gemm_rs, bench_layout, bench_mha, bench_moe
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    for mod in (bench_layout, bench_gemm, bench_mha, bench_moe, bench_gemm_rs):
        for line in mod.run():
            print(line)
            sys.stdout.flush()


if __name__ == "__main__":
    main()
