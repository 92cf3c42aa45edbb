"""Device milliseconds per traced decode step in the program's
``ssm_decode`` scope: the SSM mixer's state update and read-out
(``axe/compile.py``)."""
from chipbench import scopes


def value(run):
    return scopes.ms_per_step(run, "ssm_decode")
