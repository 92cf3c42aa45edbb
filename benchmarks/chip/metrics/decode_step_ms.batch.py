"""Device milliseconds per execution of the compiled decode-step
program (``ServeEngine.decode_fn``) in the trace."""
from chipbench import reduce_trace


def value(run):
    if run.trace is None:
        return None
    name = getattr(run.engine.decode_fn(), "__name__", "")
    ns, n = reduce_trace.program_ns(run.trace, name)
    return ns * 1e-6 / n if n else None
