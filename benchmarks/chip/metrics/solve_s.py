"""Host seconds of the first ``ServeEngine.decode_fn()`` build: the
layout solve of the decode graph and its lowering plan."""


def value(run):
    return run.solve_s
