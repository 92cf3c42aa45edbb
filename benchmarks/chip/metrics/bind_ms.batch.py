"""Device milliseconds per traced decode step in the program's ``bind``
and ``restack`` scopes (``ServeEngine.decode_fn``): the slices that bind
the stacked weights and cache onto the graph's per-layer inputs, the
tied head's transpose, and the stacking of the new cache."""
from chipbench import scopes


def value(run):
    return scopes.ms_per_step(run, "bind", "restack")
