"""Device milliseconds per traced decode step in the program's
``cache_update`` scope: the per-layer writes of the new K/V rows into
the cache (``axe/compile.py``)."""
from chipbench import scopes


def value(run):
    return scopes.ms_per_step(run, "cache_update")
