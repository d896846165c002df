"""Hot-path tracing: nestable spans on the profiler's clock, with a
Perfetto/chrome-tracing export.

While tracing is enabled every span also opens a
``jax.profiler.TraceAnnotation`` of the same name and arguments, so a span
opened inside a ``jax.profiler`` session lands in that session's
``.xplane.pb`` beside the device's operations, on the same clock: an idle
gap on the device can be laid against the host work that spans it. A span
only marks host work that happens anyway; nothing here waits for the
device.

Spans are also recorded as chrome-tracing *complete events* (``"ph": "X"``)
with microsecond timestamps, so the export loads directly in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``. Nesting comes for free:
chrome's trace viewer stacks events on the same tid by containment, and a
thread-local depth counter is recorded in ``args.depth`` for tools that
want it explicitly.

Disabled (the default), :func:`span` returns a shared null context — one
boolean read per call site, no allocation, no clock reads — so tracing can
stay compiled into every hot path.
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax


class _NullSpan:
    """Shared do-nothing context for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "args", "t0", "annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        tl = self.tracer._tls
        tl.depth = getattr(tl, "depth", 0) + 1
        self.annotation = jax.profiler.TraceAnnotation(self.name,
                                                       **self.args)
        self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_us = (time.perf_counter_ns() - self.t0) / 1e3
        self.annotation.__exit__(*exc)
        tl = self.tracer._tls
        depth = getattr(tl, "depth", 1)
        tl.depth = depth - 1
        args = dict(self.args)
        args["depth"] = depth - 1
        self.tracer._events.append({
            "name": self.name, "ph": "X", "cat": "cream",
            "ts": self.t0 / 1e3, "dur": dur_us,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": args,
        })
        return False


class Tracer:
    """An event buffer. The process-global one is :data:`TRACER`."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self._events: list[dict] = []
        self._tls = threading.local()

    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL
        return _Span(self, name, args)

    @property
    def events(self) -> list[dict]:
        return self._events

    def extend(self, events: list[dict]) -> None:
        """Append pre-built chrome-tracing events (e.g. CREAM-Lens counter
        tracks, ``"ph": "C"``) so they export alongside the spans.
        Unconditional: exporters inject into a buffer they already own."""
        self._events.extend(events)

    def reset(self) -> None:
        self._events = []

    def to_dict(self) -> dict:
        return {"traceEvents": list(self._events),
                "displayTimeUnit": "ms"}

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)

    def span_names(self) -> set[str]:
        return {e["name"] for e in self._events}


#: The process-global tracer every subsystem emits into.
TRACER = Tracer(enabled=False)


def enabled() -> bool:
    return TRACER.enabled


def enable(on: bool = True) -> None:
    TRACER.enabled = on


def disable() -> None:
    TRACER.enabled = False


def span(name: str, **args):
    """Open a span on the global tracer (null context when disabled)."""
    if not TRACER.enabled:
        return _NULL
    return _Span(TRACER, name, args)


def reset() -> None:
    TRACER.reset()


def export(path: str) -> None:
    TRACER.export(path)
