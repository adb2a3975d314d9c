"""Host spans of the scheduler's tick, on the profiler's clock.

Spans go through ``jax.profiler.TraceAnnotation`` and nothing else: while a
trace is active (``jax.profiler.trace(dir)``) the profiler keeps them beside
the device ops and writes them at ``stop_trace``; with no trace active a tick
costs one ``TraceAnnotation.is_enabled()`` check and records nothing.  Every
span carries the tick it belongs to as its ``tick`` argument.

``TickSpans`` holds ``serve.tick`` and the one phase open inside it: opening
a phase closes the one before, so a tick's phases are consecutive children
of its ``serve.tick``.  ``child`` nests one span in whatever is open, for the
waits on the device (``serve.readback``) wherever in the tick they fall.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


class TickSpans:
    """``serve.tick`` of the current tick and its open phase."""

    __slots__ = ("_tick", "_phase", "_t")

    def __init__(self):
        self._tick = self._phase = None     # open TraceAnnotations
        self._t = 0

    def open(self, t: int) -> None:
        """Open ``serve.tick`` of tick ``t`` (after ``close``)."""
        if TraceAnnotation.is_enabled():
            self._t = t
            self._tick = TraceAnnotation("serve.tick", tick=t)
            self._tick.__enter__()

    def phase(self, name: str | None) -> None:
        """Close the open phase; open ``name`` (None: leave none open)."""
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
            self._phase = None
        if name is not None and self._tick is not None:
            self._phase = TraceAnnotation(name, tick=self._t)
            self._phase.__enter__()

    def child(self, name: str):
        """A context manager: the span ``name`` nested in the open one (a
        no-op when no ``serve.tick`` is open)."""
        if self._tick is None:
            return _OFF
        return TraceAnnotation(name, tick=self._t)

    def close(self) -> None:
        """Close the open phase and ``serve.tick``."""
        self.phase(None)
        if self._tick is not None:
            self._tick.__exit__(None, None, None)
            self._tick = None
