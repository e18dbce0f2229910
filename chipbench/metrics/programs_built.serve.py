"""Executables the program built (compiled, or loaded from the persistent
cache) before the window opened: every prompt shape of the pool, every
decode batch size and pack split, and the lead-in's."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "programs_built.serve", "programs", "serving step", "program_counter",
    "setup_s")


def read(run):
    return run.counters.get("setup_programs") or None
