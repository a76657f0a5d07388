"""Spans: timed, traced stages of the serving path, and the compiles in them.

A `span` is one stage of work on one thread (``engine.plan``,
``pump.lock_wait``, ...).  It does three things at once:

* opens a ``jax.profiler.TraceAnnotation`` of its name, so a running
  profiler session records it on the profiler's own clock, the clock of
  the device planes (the ids given as keywords, such as ``round=`` or
  ``ticket=``, become the event's stats).  With no session running an
  annotation costs next to nothing;
* measures its elapsed seconds on the clock it is given (the engine's
  clock, so a fake clock in a test drives it) and, when ``counter`` is
  given, adds them to that counter of the sink;
* counts the XLA compiles its thread ran while it was open
  (``compiles``, ``compile_s``), from one process-wide
  ``jax.monitoring`` listener.

The compile listener is registered once per process, on the first call
of `compile_tally`; every span calls it on entry, so the listener is in
place before any span can observe a compile.  JAX reports an
executable's build on the thread that asked for it, whether it compiled
or read the persistent compilation cache, so the tally is kept per
thread: a pump thread's round never counts a compile another thread ran.
"""

from __future__ import annotations

import threading

from jax import monitoring
from jax.profiler import TraceAnnotation

#: the ``jax.monitoring`` duration event of one executable's build
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Tally(threading.local):
    n = 0
    seconds = 0.0


_tally = _Tally()
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        _tally.n += 1
        _tally.seconds += duration


def compile_tally() -> tuple[int, float]:
    """``(compiles, seconds)`` the calling thread has run since the
    listener was registered (by the first call of this function in the
    process).  Differences of two readings count the compiles between
    them."""
    global _listening
    if not _listening:
        with _listen_lock:
            if not _listening:
                monitoring.register_event_duration_secs_listener(_on_duration)
                _listening = True
    return _tally.n, _tally.seconds


class span:
    """A traced, timed stage; see the module docstring.

    ::

        with span(sink, "engine.plan", clock, "engine.plan_s",
                  round=7) as sp:
            plan = overlay.plan(...)
        sp.elapsed, sp.compiles, sp.compile_s   # read after exit

    The counter is added to (and the fields set) on exit, exceptions
    included.
    """

    __slots__ = ("_sink", "_clock", "_counter", "_trace", "_t0", "_c0",
                 "elapsed", "compiles", "compile_s")

    def __init__(self, sink, name: str, clock, counter: str | None = None,
                 **ids):
        self._sink = sink
        self._clock = clock
        self._counter = counter
        self._trace = TraceAnnotation(name, **ids)
        self.elapsed = 0.0
        self.compiles = 0
        self.compile_s = 0.0

    def __enter__(self) -> "span":
        self._trace.__enter__()
        self._c0 = compile_tally()
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = self._clock() - self._t0
        n, s = compile_tally()
        self.compiles, self.compile_s = n - self._c0[0], s - self._c0[1]
        if self._counter is not None:
            self._sink.inc(self._counter, self.elapsed)
        self._trace.__exit__(*exc)
