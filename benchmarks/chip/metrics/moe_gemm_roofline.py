"""Least time of the traced decode steps' expert products over the
summed device time of the ``moe_gemm_expert_gemm`` kernel, in percent.
Per layer and step, from the program's expert-load counter: 6·d·f FLOPs
per routed (token, held expert) pair; bytes: each held expert with a
token reads its three matrices once, and each pair its row in and out
(``reference.expert_least_s``)."""
from chipbench import expert_load, reduce_trace

KERNEL = "moe_gemm_expert_gemm"


def value(run):
    if run.trace is None:
        return None
    ns = reduce_trace.kernel_ns(run.trace, KERNEL)
    loads = expert_load.traced(run, ("decode",))
    if ns <= 0 or not loads:
        return None
    least = sum(run.work.ref.expert_least_s(run.sizes, int(pairs), int(active),
                                            run.peaks.flops_bf16, run.peaks.hbm_bw)
                for load in loads for pairs, active in load)
    return 100.0 * least / (ns * 1e-9)
