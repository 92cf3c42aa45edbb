"""Device idle milliseconds inside the batcher's ``admit`` span and its
children (``prefill``, ``first_token``, ``slot_write``) per admission in
the traced window."""
from chipbench import scopes


def value(run):
    if run.trace is None:
        return None
    return scopes.idle_ms_per_span(run.trace, scopes.ADMIT_SPANS)
