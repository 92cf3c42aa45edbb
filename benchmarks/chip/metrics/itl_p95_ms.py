"""95th percentile of every gap between consecutive output tokens of
every request, both tokens inside the window."""
from chipbench import stats


def value(run):
    return stats.percentile(run.gaps, 95) * 1e3 if run.gaps else None
