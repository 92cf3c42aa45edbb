"""Model FLOPs of every prompt and output token processed in the traced
window over the window's seconds times the chip's bf16 peak, in percent:
the whole serving step's share of the peak. Dense work (attention,
router, head) from ``chipbench.work``; the held experts' work from the
program's expert-load counter (6·d·f per routed pair)."""
from chipbench import expert_load


def value(run):
    loads = expert_load.traced(run)
    if not loads:
        return None
    flops = sum(run.work.decode_flops(p) for p in run.traced_decodes() if p)
    flops += sum(run.work.prefill_flops(s) for s in run.traced_prompts())
    flops += sum(run.work.ref.expert_work(run.sizes, int(pairs), int(active))[0]
                 for load in loads for pairs, active in load)
    return 100.0 * flops / (run.trace.window_s * run.peaks.flops_bf16)
