"""Model FLOPs of every prompt and output token processed in the traced
window (``chipbench.work``) over the window's seconds times the chip's
bf16 peak, in percent: the whole serving step's share of the peak."""


def value(run):
    if run.trace is None:
        return None
    flops = sum(run.work.decode_flops(p) for p in run.traced_decodes() if p)
    flops += sum(run.work.prefill_flops(s) for s in run.traced_prompts())
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.peaks.flops_bf16)
