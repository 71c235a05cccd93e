"""Enable-gated instrumentation facade — the only obs API hot paths touch.

One span, two sinks.  ``span(name)`` writes a host event named ``name`` into
the ``jax.profiler`` trace (a ``TraceAnnotation``), so a span shares the
profiler's clock with the device's ops; with obs enabled it also records
into the ``Tracer`` (its own ``perf_counter`` clock, Chrome-trace export).
The annotation passes the name only: the span's args go to the ``Tracer``.

Cost when obs is disabled: counters, gauges and histograms are a single
module-flag test; ``span()`` adds one test of whether a profiler session is
active (about 20 ns), and returns a shared null context when none is (no
allocation, no clock read) — an annotation would record nothing then.
Under an active profiler session it enters the annotation, which records
an event: a few microseconds of host time per span.

A span times host code.  Around a jitted call or a kernel wrapper (the
``kernels/<name>`` spans of ``kernels/ops.py``) it times the dispatch, not
the device's work, unless the host waits for the result inside it.

Instrumentation must sit *around* ``jax.jit``-traced calls, never inside
them — a traced function runs as compiled XLA where Python side effects
do not execute (and would otherwise bake constants into the trace), so
callers record around ``jit_step(...)`` / ``self._step(...)`` boundaries.
Device-side regions are named with ``jax.named_scope`` instead.

Enable globally with ``REPRO_OBS=1`` in the environment, or per-scope::

    from repro import obs
    with obs.enabled_scope() as (registry, tracer):
        ...  # instrumented code publishes into this private pair

or imperatively with :func:`enable` / :func:`disable`.
"""
from __future__ import annotations

import functools
import os
import time
from contextlib import contextmanager
from typing import Callable, Iterator, Optional, Tuple

from jax.profiler import TraceAnnotation

from . import metrics as _metrics
from . import trace as _trace

_enabled: bool = os.environ.get("REPRO_OBS", "").lower() in ("1", "true", "on")
_registry: _metrics.Registry = _metrics.REGISTRY
_tracer: _trace.Tracer = _trace.TRACER


def enabled() -> bool:
    return _enabled


def registry() -> _metrics.Registry:
    """The registry instrumentation currently publishes into."""
    return _registry


def tracer() -> _trace.Tracer:
    return _tracer


def enable(registry: Optional[_metrics.Registry] = None,
           tracer: Optional[_trace.Tracer] = None) -> None:
    """Turn instrumentation on, optionally onto private sinks."""
    global _enabled, _registry, _tracer
    if registry is not None:
        _registry = registry
    if tracer is not None:
        _tracer = tracer
    _enabled = True


def disable() -> None:
    """Turn instrumentation off and restore the default global sinks."""
    global _enabled, _registry, _tracer
    _enabled = False
    _registry = _metrics.REGISTRY
    _tracer = _trace.TRACER


@contextmanager
def disabled_scope() -> Iterator[None]:
    """Suppress recording inside the block; restore prior state on exit.

    For meta-tooling (e.g. ``repro.analysis``) that *executes* instrumented
    code paths on synthetic inputs — their series must not leak into the
    surrounding run's registry.
    """
    global _enabled
    prev = _enabled
    _enabled = False
    try:
        yield
    finally:
        _enabled = prev


@contextmanager
def enabled_scope(registry: Optional[_metrics.Registry] = None,
                  tracer: Optional[_trace.Tracer] = None
                  ) -> Iterator[Tuple[_metrics.Registry, _trace.Tracer]]:
    """Enable onto fresh (or given) sinks; restore prior state on exit."""
    global _enabled, _registry, _tracer
    prev = (_enabled, _registry, _tracer)
    reg = registry if registry is not None else _metrics.Registry()
    trc = tracer if tracer is not None else _trace.Tracer()
    enable(reg, trc)
    try:
        yield reg, trc
    finally:
        _enabled, _registry, _tracer = prev


# ---------------------------------------------------------------------------
# Recording helpers (no-ops when disabled)
# ---------------------------------------------------------------------------

def counter_inc(name: str, amount: float = 1, **labels) -> None:
    if not _enabled:
        return
    _registry.counter(name, **labels).inc(amount)


def gauge_set(name: str, value: float, **labels) -> None:
    if not _enabled:
        return
    _registry.gauge(name, **labels).set(value)


def hist_observe(name: str, value: float, **labels) -> None:
    if not _enabled:
        return
    _registry.histogram(name, **labels).observe(value)


class _NullSpan:
    """Inert stand-in yielded by a span that the ``Tracer`` does not record."""
    __slots__ = ()
    cycles = 0

    def add_cycles(self, n: int) -> None:
        pass

    def set(self, **kwargs) -> None:
        pass


class _NullCtx:
    __slots__ = ()
    _span = _NullSpan()

    def __enter__(self) -> _NullSpan:
        return self._span

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullCtx()


class _Annotated:
    """The profiler's annotation alone: the disabled span under a session."""
    __slots__ = ("_annotation",)

    def __init__(self, name: str):
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> _NullSpan:
        self._annotation.__enter__()
        return _NullCtx._span

    def __exit__(self, *exc) -> bool:
        self._annotation.__exit__(*exc)
        return False


@contextmanager
def _recorded(name: str, args: dict) -> Iterator[_trace.Span]:
    with TraceAnnotation(name), _tracer.span(name, **args) as sp:
        yield sp


def span(name: str, **args):
    """Context manager: a host span in the profiler's trace, and a live
    ``Tracer`` span when obs is enabled; the shared no-op context when
    neither records."""
    if _enabled:
        return _recorded(name, args)
    if TraceAnnotation.is_enabled():
        return _Annotated(name)
    return _NULL_CTX


def instrumented(name: Optional[str] = None, **labels
                 ) -> Callable[[Callable], Callable]:
    """Decorator: wrap calls in a span + ``<name>_ms`` latency histogram.

    Disabled, the wrapper costs what a disabled ``span()`` costs.  Apply to
    *host-side* functions only — never to a function that will itself be
    ``jax.jit``-traced (see module docstring).
    """
    def deco(fn: Callable) -> Callable:
        metric = name if name is not None else fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not _enabled:
                with span(metric):
                    return fn(*a, **kw)
            t0 = time.perf_counter()
            with _recorded(metric, labels):
                out = fn(*a, **kw)
            _registry.histogram(f"{metric}_ms", **labels).observe(
                (time.perf_counter() - t0) * 1e3)
            return out

        return wrapper

    return deco
