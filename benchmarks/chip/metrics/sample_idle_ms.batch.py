"""Device idle milliseconds inside the batcher's ``sample`` span (the
batch's sampling and its device-to-host read of the tokens) per such
span in the traced window."""
from chipbench import scopes


def value(run):
    if run.trace is None:
        return None
    return scopes.idle_ms_per_span(run.trace, ("sample",))
