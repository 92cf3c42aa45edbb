"""Output tokens emitted in the window over the window's seconds."""
from chipbench import stats


def value(run):
    return stats.rate(run.tokens, run.window_s) if run.tokens else None
