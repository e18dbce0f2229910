"""Share of the window's queried rows answered from materialized models
(the rest are scanned)."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "reuse_share.analytics", "%", "planner + store", "program_counter", "fits_per_s")


def read(run):
    q = run.counters.get("rows_queried", 0)
    return 100.0 * (1.0 - run.counters["rows_scanned"] / q) if q else None
