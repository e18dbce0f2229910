"""Share of the window's prompt tokens built from stored KV segments (the
rest are computed): the planner's plans over the store's contents."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "reuse_share.serve", "%", "planner + store", "program_counter", "ttft_p85_ms")


def read(run):
    r = run.counters.get("tokens_reused", 0)
    c = run.counters.get("tokens_computed", 0)
    return 100.0 * r / (r + c) if r + c else None
