"""Least time of the traced decode steps' weight products, the output
head included (``Work.matmul_least_s`` at the live slot count), over the
device time in the program's ``matmul`` scope (the kernel and whatever
XLA runs for a product, such as a head no tiling divides), in percent."""
from chipbench import scopes


def value(run):
    ops = scopes.step_ops(run)
    ns = scopes.scope_ns(ops, "matmul") if ops else 0.0
    steps = [p for p in run.traced_decodes() if p]
    if ns <= 0 or not steps:
        return None
    least = sum(run.work.matmul_least_s(len(p), run.peaks.flops_bf16, run.peaks.hbm_bw)
                for p in steps)
    return 100.0 * least / (ns * 1e-9)
