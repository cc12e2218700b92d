"""Stage names for the profiler: host spans and device scopes, ``vdms.<stage>``.

* :func:`span` times a piece of host code under a
  ``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` trace shows it on
  the same clock as the device's operations. Its ``seconds`` hold the elapsed
  time once it closes, and while a :func:`collect` is open in the current
  context (a ``contextvars`` variable: each thread has its own) the seconds
  are also added under its name to that collector's dict. A span that raises
  still records, and the exception goes on.
* :func:`scope` names code inside ``jit``: every operation traced under it
  carries ``vdms.<stage>`` in the ``op_name`` of its HLO metadata, and a
  fusion carries its root's. It is metadata only: the compiled program, its
  fusions and its results are the same with or without it.

Neither has a switch: a ``TraceAnnotation`` with no trace running costs about
a microsecond.
"""
from __future__ import annotations

import contextlib
import contextvars
import time

import jax
from jax.profiler import TraceAnnotation

PREFIX = "vdms."

_SINK: contextvars.ContextVar = contextvars.ContextVar("repro_obs_sink", default=None)


class span:
    """``with span("search.prep") as s: ...`` -- a host span; ``s.seconds``
    is its elapsed time once it has closed."""

    __slots__ = ("name", "seconds", "_annotation", "_sink", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds = None

    def __enter__(self) -> "span":
        self._sink = _SINK.get()
        self._annotation = TraceAnnotation(PREFIX + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._sink is not None:
            self._sink[self.name] = self._sink.get(self.name, 0.0) + self.seconds
        return False


@contextlib.contextmanager
def collect():
    """Sum the seconds of every span closed in this context, by name, into
    the dict it yields. An inner ``collect`` takes the spans of its own block."""
    sink: dict = {}
    token = _SINK.set(sink)
    try:
        yield sink
    finally:
        _SINK.reset(token)


def scope(name: str):
    """``with scope("gid_map"): ...`` inside traced code: a ``jax.named_scope``."""
    return jax.named_scope(PREFIX + name)
