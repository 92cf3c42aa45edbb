"""Least time of the traced decode steps' weight products, the output
head included (``Work.matmul_least_s`` at the live slot count), over
the summed device time of the ``matmul_tile`` kernel, in percent. Only
for a cell whose every weight product runs in that kernel."""
from chipbench import reduce_trace


def value(run):
    if run.trace is None:
        return None
    ns = reduce_trace.kernel_ns(run.trace, "matmul_tile")
    steps = [p for p in run.traced_decodes() if p]
    if ns <= 0 or not steps:
        return None
    least = sum(run.work.matmul_least_s(len(p), run.peaks.flops_bf16, run.peaks.hbm_bw)
                for p in steps)
    return 100.0 * least / (ns * 1e-9)
