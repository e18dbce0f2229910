"""Executables the program built (compiled, or loaded from the persistent
cache) per distinct query in the warm-up pass: the kernels' wrappers pad
each gap to its exact length, so each new gap length builds programs."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "compiles_per_query.analytics", "programs", "analytics kernels' wrappers",
    "program_counter", "setup_s")


def read(run):
    q = run.counters.get("warm_queries", 0)
    return run.counters["warm_programs"] / q if q else None
