"""Least time of the traced decode steps' attention, K/V read up to each
slot's live position (``Work.decode_attention_least_s``), over the
summed device time of the ``flash_attention_decode`` kernel, in
percent."""
from chipbench import reduce_trace


def value(run):
    if run.trace is None:
        return None
    ns = reduce_trace.kernel_ns(run.trace, "flash_attention_decode")
    steps = [p for p in run.traced_decodes() if p]
    if ns <= 0 or not steps:
        return None
    least = sum(run.work.decode_attention_least_s(p, run.peaks.flops_bf16, run.peaks.hbm_bw)
                for p in steps)
    return 100.0 * least / (ns * 1e-9)
