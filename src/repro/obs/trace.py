"""Host span tracing: lightweight wall-clock spans with Chrome-trace
export.

Spans record into a bounded in-process ring buffer (no I/O on the hot
path, no background thread); :func:`export_chrome` writes the buffer as
Chrome-trace JSON ("X" complete events) loadable in ``chrome://tracing``
/ Perfetto.  Every span is also a ``jax.profiler.TraceAnnotation`` of
the same name, so while a JAX profiler trace runs the spans land in its
host plane on the device trace's clock (about a microsecond a span when
no profiler runs).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import threading
import time


@functools.lru_cache(maxsize=None)
def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on the first span so
    that importing this module pulls in no jax."""
    import jax

    return jax.profiler.TraceAnnotation


class SpanTracer:
    """Bounded ring buffer of completed spans; counts the spans it
    evicts once full."""

    def __init__(self, maxlen: int = 65536):
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._dropped = 0

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Record a wall-clock span around the enclosed block, and a
        profiler annotation ``name`` around the same block.

        Extra keyword arguments land in the event's ``args`` dict
        (small JSON-serializable values: chunk index, schedule mode),
        never in the annotation.  The dict is yielded, so the block can
        add what it learns while it runs (bytes moved)."""
        with _annotation()(name):
            t0 = time.perf_counter_ns()
            try:
                yield args
            finally:
                dur = time.perf_counter_ns() - t0
                ev = {"name": name, "ts_ns": t0, "dur_ns": dur,
                      "tid": threading.get_ident()}
                if args:
                    ev["args"] = args
                with self._lock:
                    if len(self._events) == self._events.maxlen:
                        self._dropped += 1
                    self._events.append(ev)

    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def dropped(self) -> int:
        """Spans evicted from the full buffer since the last clear."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def export_chrome(self, path: str) -> str:
        """Write the buffered spans as Chrome-trace JSON ("X" events,
        microsecond timestamps) and return the path."""
        pid = os.getpid()
        events = []
        for ev in self.spans():
            out = {"name": ev["name"], "ph": "X", "pid": pid,
                   "tid": ev["tid"], "ts": ev["ts_ns"] / 1e3,
                   "dur": ev["dur_ns"] / 1e3}
            if "args" in ev:
                out["args"] = ev["args"]
            events.append(out)
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as fh:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                      fh, indent=1)
            fh.write("\n")
        return path


TRACER = SpanTracer()

span = TRACER.span
spans = TRACER.spans
dropped = TRACER.dropped
clear = TRACER.clear
export_chrome = TRACER.export_chrome

