"""Device milliseconds per traced decode step in the expert layer: the
ops of the graph nodes ``L<i>.moe_*`` (``axe/graphs.py``) — the dispatch
with its routing, the expert products (``moe_gemm``), the activation
between them, and the combine."""
import re

from chipbench import reduce_trace, scopes

_EXPERT_NODE = re.compile(r"L\d+\.moe_")


def _expert_layer(path: str) -> bool:
    parts = path.split("/")   # jit(step)/<kind>/<node>/...
    return len(parts) > 2 and _EXPERT_NODE.match(parts[2]) is not None


def value(run):
    ops = scopes.step_ops(run)
    if not ops:
        return None
    ns = sum(op.end - op.start for op in ops if _expert_layer(op.scope))
    n = reduce_trace.program_ns(run.trace, scopes.step_name(run))[1]
    return ns * 1e-6 / n if ns > 0 and n else None
