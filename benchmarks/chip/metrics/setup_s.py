"""Process start to the first timed request: weights, solve, compile
(or compile-cache reads) and warm-up."""


def value(run):
    return run.setup_s
