"""Program-independent pieces of the chip benchmark: cell discovery,
traffic generation, wall-clock statistics, the work model, the peak
table, trace reduction and the correctness comparison. Nothing here
imports the program (``src/repro``) except ``serve_loop``, which drives
it."""
