"""Spans and counters of the GAS program, on the device trace's clock.

    with spans.span("gas/plan/emit") as sp:
        ...
    sp.seconds                          # this span's host seconds
    spans.count("gas/plan/upload_bytes", n)

Every span enters a `jax.profiler.TraceAnnotation` of its name, so in any
`jax.profiler` trace it lands in the host plane on the same clock as the
device's ops, and a gap in the device's work can be named by the span
around it. Every name starts with `gas/`.

Recording is off by default: spans then only time themselves and
annotate the trace, counters do nothing, and nothing is kept.
`start()` turns it on: each span appends `(name, parent index, start_ns,
end_ns)` to an in-memory list (the parent is the innermost span open in
the same thread, -1 at the top), counters add up, and one `gc.callbacks`
hook records each collection of Python's oldest generation as a span
`gas/gc`. `stop()` removes the hook and returns what was recorded. The
list covers what no trace does, such as set-up before a profiler
session starts; its clock is `time.perf_counter_ns`, not the trace's.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

Record = Tuple[str, int, int, int]   # name, parent index, start ns, end ns


class Recording(NamedTuple):
    spans: List[Record]
    counters: Dict[str, int]

    def seconds(self, name: str) -> float:
        """Summed seconds of the closed spans called `name`."""
        return sum(e - s for n, _, s, e in self.spans
                   if n == name and e >= 0) / 1e9


_records: Optional[List[list]] = None   # None while recording is off
_counters: Dict[str, int] = {}
_local = threading.local()              # .open: indices of open spans


def _open() -> list:
    if not hasattr(_local, "open"):
        _local.open = []
    return _local.open


class span:
    """Context manager: a `TraceAnnotation` named `name`, timed; recorded
    while recording is on. `.seconds` holds its duration after exit."""

    def __init__(self, name: str):
        self.name = name
        self.seconds: Optional[float] = None

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._into = _records
        self._t0 = time.perf_counter_ns()
        if self._into is not None:
            stack = _open()
            self._index = len(self._into)
            self._into.append([self.name, stack[-1] if stack else -1,
                               self._t0, -1])
            stack.append(self._index)
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self.seconds = (t1 - self._t0) / 1e9
        if self._into is not None:
            self._into[self._index][3] = t1
            stack = _open()
            if self._into is _records and stack and stack[-1] == self._index:
                stack.pop()
        self._ann.__exit__(*exc)


def count(name: str, n: int) -> None:
    """Adds `n` to the counter `name` while recording is on."""
    if _records is not None:
        _counters[name] = _counters.get(name, 0) + int(n)


_gc_span: Optional[span] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if info.get("generation") != 2:
        return
    if phase == "start":
        _gc_span = span("gas/gc").__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def start() -> None:
    """Turns recording on with empty records and counters."""
    global _records
    _records = []
    _counters.clear()
    _open().clear()
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def stop() -> Recording:
    """Turns recording off; returns the spans and counters recorded since
    `start()` (a span still open has `end_ns` -1)."""
    global _records
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    out = Recording([tuple(r) for r in (_records or [])],
                    dict(_counters))
    _records = None
    _counters.clear()
    return out
