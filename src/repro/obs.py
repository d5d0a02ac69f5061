"""Spans of the serving engines' host work, recorded in memory.

Off by default. :func:`start` switches recording on and :func:`stop`
switches it off and returns what was recorded in between. With recording
off, a span site checks one flag and gets a shared no-op back: no clock
call, and nothing of the recorder's is allocated.

A :class:`Span` holds its name, start and end in ``time.time_ns()``, its id,
its parent's id (the innermost span open on the same thread: the gateway
runs each engine on a worker thread) and its attributes (``rid`` wherever a
request is known, so one request's spans share it). ``time.time_ns()`` is
the wall clock on which a profiler trace's ``profile_start_time`` is given
and from which its device events are offsets, so spans and device events
share one clock.

While recording, each span also enters a ``jax.profiler.TraceAnnotation`` of
its name, so a profiler capture shows it, and every backend compile
(persistent-cache loads included) becomes a ``jax.compile`` span with the
compiled function's name as attribute ``fun``.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_on = False
_spans: list = []
_ids = itertools.count(1)
_local = threading.local()
_listening = False


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                    # ns, time.time_ns()
    end: int
    id: int
    parent: int | None            # id of the enclosing span on its thread
    attrs: dict


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _record(name, start, end, parent, attrs, span_id=None):
    if _on:
        _spans.append(Span(name, start, end,
                           next(_ids) if span_id is None else span_id,
                           parent, attrs))


class _Open:
    """A span being recorded: a context manager, or, opened at an explicit
    ``start``, closed by :meth:`close`."""

    __slots__ = ("name", "start", "attrs", "id", "parent", "_note")

    def __init__(self, name: str, start: int | None, attrs: dict):
        self.name, self.start, self.attrs = name, start, attrs
        self.id = next(_ids)
        stack = _stack()
        self.parent = stack[-1] if stack else None

    def __enter__(self):
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        _stack().append(self.id)
        if self.start is None:
            self.start = time.time_ns()
        return self

    def __exit__(self, *exc):
        end = time.time_ns()
        _stack().pop()
        self._note.__exit__(*exc)
        _record(self.name, self.start, end, self.parent, self.attrs, self.id)

    def close(self):
        _record(self.name, self.start, time.time_ns(), self.parent,
                self.attrs, self.id)


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def close(self):
        return None


_NOOP = _Noop()


def span(name: str, start: int | None = None, **attrs):
    """``with span(name, rid=...):`` records the block. With ``start`` (a
    :func:`now` taken earlier, perhaps in another call) the span runs from
    there to its :meth:`close`, and is the child of the span open where it
    closes."""
    if not _on:
        return _NOOP
    return _Open(name, start, attrs)


def now() -> int | None:
    """The recorder's clock while recording, else None (no clock call)."""
    return time.time_ns() if _on else None


def _on_time_span(event, start_s, end_s, **kw):
    if _on and event == COMPILE_EVENT:
        stack = _stack()
        _record("jax.compile", int(start_s * 1e9), int(end_s * 1e9),
                stack[-1] if stack else None, {"fun": kw.get("fun_name")})


def start():
    """Switch recording on, with no spans recorded yet."""
    global _on, _spans, _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _listening = True
    _spans = []
    _on = True


def stop() -> list:
    """Switch recording off; returns the spans recorded since :func:`start`,
    in the order they ended."""
    global _on
    _on = False
    return _spans
