"""Host spans and counters at the analytics path's layer boundaries.

``span(name)`` times a block and exposes its ``.seconds`` once it exits;
``ExecTimings`` is filled from those seconds.  Tracing is off by default:
a span then reads the clock twice and keeps nothing.

After ``enable()`` every span is also kept in memory as a record
``(name, start_ns, end_ns, parent, query)`` (``parent``: the index of the
enclosing record, or None; ``query``: the id of the enclosing
``repro.query`` span), and opens a ``jax.profiler.TraceAnnotation`` of its
name, so that a profiler trace shows it on the host plane on the same
clock as the device's programs.  Every executable JAX builds while tracing
is on is counted under the innermost open span.  ``summary()`` reduces the
records to a count, total and self seconds per span name, with the
counters; a span's self time is its duration less what its child spans
cover.

The state is per process and assumes spans open and close on one thread,
as the analytics path does.  Every span name starts with ``repro.``.
"""
from __future__ import annotations

import time
from typing import Optional

#: the ``jax.monitoring`` event recorded once per executable built
#: (compiled, or loaded from the persistent cache)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NO_SPAN = "no program span"

_on = False
_listening = False
_annotation = None          # jax.profiler.TraceAnnotation, once enabled
_records: list = []         # [name, start_ns, end_ns | None, parent, query]
_stack: list = []           # indices of the open records, innermost last
_counters: dict = {}
_compiles: dict = {}
_queries = 0


class span:
    """Time a block: ``with span("repro.plan") as s: ...; s.seconds``.

    ``query=True`` marks the span of one query: it draws a new query id,
    which every span opened inside it carries.
    """

    __slots__ = ("name", "seconds", "_query", "_t0", "_rec", "_ann")

    def __init__(self, name: str, *, query: bool = False) -> None:
        self.name = name
        self.seconds = 0.0
        self._query = query
        self._rec: Optional[list] = None

    def __enter__(self) -> "span":
        if _on:
            self._open()
        self._t0 = time.perf_counter_ns()
        if self._rec is not None:
            self._rec[1] = self._t0
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self.seconds = (t1 - self._t0) * 1e-9
        if self._rec is not None:
            self._close(t1)

    def _open(self) -> None:
        global _queries
        parent = _stack[-1] if _stack else None
        if self._query:
            _queries += 1
            query = _queries
        else:
            query = _records[parent][4] if parent is not None else None
        self._rec = [self.name, 0, None, parent, query]
        _stack.append(len(_records))
        _records.append(self._rec)
        self._ann = _annotation(self.name)
        self._ann.__enter__()

    def _close(self, t1: int) -> None:
        self._ann.__exit__(None, None, None)
        if _stack and _records[_stack[-1]] is self._rec:
            _stack.pop()
            self._rec[2] = t1
        self._rec = None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while tracing is on."""
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def _on_duration(event: str, duration: float, **_) -> None:
    if _on and event == _COMPILE_EVENT:
        name = _records[_stack[-1]][0] if _stack else NO_SPAN
        _compiles[name] = _compiles.get(name, 0) + 1


def enable() -> None:
    """Keep records and write profiler annotations from here on."""
    global _on, _listening, _annotation
    import jax

    _annotation = jax.profiler.TraceAnnotation
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    _on = True


def disable() -> None:
    """Back to timing only; the records kept so far stay until ``reset``."""
    global _on
    _on = False


def reset() -> None:
    """Drop every record, counter and query id."""
    global _queries
    _records.clear()
    _stack.clear()
    _counters.clear()
    _compiles.clear()
    _queries = 0


def records() -> list[tuple]:
    """Every span kept, as ``(name, start_ns, end_ns, parent, query)``;
    ``end_ns`` is None while the span is open."""
    return [tuple(r) for r in _records]


def summary() -> dict:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters": {...},
    "compiles": {innermost span name or NO_SPAN: executables built}}``
    over the closed spans."""
    child = [0] * len(_records)
    for _, t0, t1, parent, _ in _records:
        if parent is not None and t1 is not None:
            child[parent] += t1 - t0
    spans: dict = {}
    for i, (name, t0, t1, _, _) in enumerate(_records):
        if t1 is None:
            continue
        s = spans.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += (t1 - t0) * 1e-9
        s["self_s"] += (t1 - t0 - child[i]) * 1e-9
    return {"spans": spans, "counters": dict(_counters),
            "compiles": dict(_compiles)}
