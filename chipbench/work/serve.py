"""Operations and needed bytes of serving a dense GQA decoder.

From the configuration's widths alone (``reference.serve.dims``): ``d``
hidden, ``H`` query heads over ``KV`` key-value heads of ``hd``, SwiGLU
width ``F``, vocabulary ``V``, ``L`` layers, bf16 (2 bytes) KV.

Attention's needed work is that of the live context, never the padded
cache: a query at position ``p`` attends ``p + 1`` keys, at ``4·H·hd``
operations per key (``q·k`` and ``p·v``, a multiply and an add each), and
the keys and values of the live positions are read once per call, with the
queries and outputs once each.
"""
from __future__ import annotations

BYTES = 2          # bf16 activations and KV


def kv_bytes_per_token(n: dict) -> int:
    """Keys and values of one position in every layer."""
    return 2 * n["KV"] * n["hd"] * BYTES * n["L"]


def matmul_flops_per_token(n: dict) -> float:
    """Weight matmuls of one token through every layer (the head apart)."""
    d, H, KV, hd, F = n["d"], n["H"], n["KV"], n["hd"], n["F"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return 2.0 * per_layer * n["L"]


def head_flops(n: dict) -> float:
    """One row of logits."""
    return 2.0 * n["d"] * n["V"]


def decode_attention(n: dict, live_tokens: int, rows: int) -> tuple[float, float]:
    """Decode attention over ``rows`` rows that attend ``live_tokens`` keys
    in all (the sum of ``pos + 1`` over the rows): (operations, bytes)."""
    ops = 4.0 * n["H"] * n["hd"] * live_tokens * n["L"]
    byt = (live_tokens * 2 * n["KV"] * n["hd"] * BYTES +
           rows * 2 * n["H"] * n["hd"] * BYTES) * n["L"]
    return ops, float(byt)


def extend_attention(n: dict, start: int, nb: int) -> tuple[float, float]:
    """Causal attention of ``nb`` new positions from ``start`` over the
    context ``[0, start + nb)``: (operations, bytes)."""
    keys = nb * start + nb * (nb + 1) // 2
    ops = 4.0 * n["H"] * n["hd"] * keys * n["L"]
    byt = ((start + nb) * 2 * n["KV"] * n["hd"] * BYTES +
           nb * 2 * n["H"] * n["hd"] * BYTES) * n["L"]
    return ops, float(byt)


def build_calls(gaps: list, prefix: int, chunk: int) -> list:
    """The calls that compute one request's prompt of ``prefix`` tokens:
    ``[(kind, start, n)]`` with kind ``prefill`` (a cold start's first
    chunk, exact length, no extend kernel) or ``extend`` (kernel).

    ``gaps`` are the plan's uncovered ranges ``(lo, hi)`` of
    ``[0, prefix - 1)``; each is cut into chunks of ``chunk`` from its own
    start, and the last prompt token is a one-token extend of its own.
    """
    calls = []
    for lo, hi in sorted(gaps):
        if lo == 0:
            first = min(chunk, hi)
            calls.append(("prefill", 0, first))
            lo = first
        while lo < hi:
            nb = min(chunk, hi - lo)
            calls.append(("extend", lo, nb))
            lo += nb
    calls.append(("extend", prefix - 1, 1))
    return calls
