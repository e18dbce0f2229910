"""Rows per batched decode call in the window: how many sessions the
scheduler coalesces into one step."""
NAME, UNIT, LAYER, SOURCE, MOVES = (
    "decode_batch_mean.serve", "rows", "scheduler", "program_counter", "itl_p90_ms")


def read(run):
    calls = run.counters.get("decode_calls", 0)
    return run.counters["decode_rows"] / calls if calls else None
