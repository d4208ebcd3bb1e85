"""Span API: ``obs.scope(routine=...)`` — the one instrumentation surface.

A *span* is a host-side named region that simultaneously

* opens a :func:`slate_tpu.utils.trace.trace_block` region (so spans land in
  the chrome-trace timeline next to the existing phase timers and the
  resilience layer's retry/fault instants), and
* records into the metrics registry on close: ``slate_spans_total`` (counter)
  and ``slate_span_seconds`` (histogram), labeled with the routine plus
  whatever labels the caller attached (dtype, shape_bucket, mesh, nb,
  method, ...).

Spans nest; a child records its parent's routine under the ``parent`` label
so nested driver compositions (gesv -> getrf -> trsm) remain attributable.
Under ``jax.jit`` a span runs once, while the call is traced: it then names the
compiled operations (``trace_block`` is a ``jax.named_scope``), and its samples
carry ``traced="true"``, so they count traces and never mix with the timings
of eager calls.

:func:`instrument` is the decorator the distributed drivers wear: it derives
the standard labels (dtype + shape bucket from the first array argument,
``pxq`` mesh from a ``ProcessGrid`` argument, ``nb``/``method`` keyword
options) and wraps the call in a scope.  Host-side overhead is a few dict
writes per *driver call* — noise against any distributed solve, and the
counters need no enable switch (unlike the trace timeline, which stays
opt-in via ``trace.on()``).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Dict, Optional

import jax

from ..utils.trace import trace_block
from .registry import REGISTRY

_stack = threading.local()

#: attribute stamped on instrumented callables (the meta-test in
#: tests/test_obs.py asserts every public parallel/ driver carries it)
INSTRUMENT_ATTR = "__obs_routine__"


def current_span() -> Optional[str]:
    """Routine name of the innermost open span on this thread (None outside)."""
    stack = getattr(_stack, "spans", None)
    return stack[-1] if stack else None


def span_depth() -> int:
    """Nesting depth of open spans on this thread (0 outside any scope)."""
    return len(getattr(_stack, "spans", ()))


class SpanHandle:
    """The object a :func:`scope` yields: a slot for the span's result.

    With ``device_sync=True`` on the scope, the recorded duration includes a
    ``block_until_ready()`` on whatever was handed to :meth:`set_result` —
    without it, an async-dispatch backend would close the span at *dispatch*
    time and the execute histogram would measure queue depth, not compute.
    """

    __slots__ = ("_result",)

    def __init__(self):
        self._result = None

    def set_result(self, value) -> None:
        """Attach the span's device result (blocked on at close when the
        scope was opened with ``device_sync=True``)."""
        self._result = value


@contextlib.contextmanager
def scope(routine: str, device_sync: bool = False, **labels):
    """Open an observability span around a routine invocation.

    ::

        with obs.scope("getrf_distributed", mesh="2x4", dtype="float32"):
            ...

    Labels are stringified; the span's duration lands in the
    ``slate_span_seconds`` histogram and its count in ``slate_spans_total``.

    ``device_sync=True`` (opt-in; the serve execute stage is the intended
    caller) makes the span block on the result attached via the yielded
    :class:`SpanHandle` before closing, so the duration is dispatch+compute
    rather than dispatch alone, and stamps a ``device_sync="true"`` label so
    synced and unsynced timings never mix in one series::

        with obs.scope("serve.execute", device_sync=True) as sp:
            sp.set_result(driver(A, B))
    """
    labels = {k: str(v) for k, v in labels.items() if v is not None}
    if device_sync:
        labels["device_sync"] = "true"
    parent = current_span()
    if parent is not None:
        labels.setdefault("parent", parent)
    stack = getattr(_stack, "spans", None)
    if stack is None:
        stack = _stack.spans = []
    stack.append(routine)
    handle = SpanHandle()
    t0 = time.perf_counter()
    try:
        with trace_block(routine, **labels):
            yield handle
            if device_sync and hasattr(handle._result, "block_until_ready"):
                handle._result.block_until_ready()
    finally:
        dur = time.perf_counter() - t0
        stack.pop()
        REGISTRY.counter(
            "slate_spans_total",
            "driver invocations, by routine and labels").inc(
                routine=routine, **labels)
        REGISTRY.histogram(
            "slate_span_seconds",
            "host wall time per driver invocation").observe(
                dur, routine=routine, **labels)


def _shape_bucket(shape) -> str:
    """Pow-2 bucket of the largest dim: the sweep label that keeps histogram
    cardinality bounded while separating 64-class from 16384-class rows."""
    try:
        top = max(int(d) for d in shape) if len(shape) else 1
    except (TypeError, ValueError):
        return "unknown"
    b = 1
    while b < top:
        b <<= 1
    return f"<={b}"


_LABEL_KWARGS = ("nb", "method", "lu_panel", "kind", "uplo", "lookahead",
                 "batch", "bucket")


def _derive_labels(args, kwargs) -> Dict[str, Any]:
    """Standard label extraction for :func:`instrument`: best-effort and
    exception-free — a driver call must never fail because of telemetry."""
    labels: Dict[str, Any] = {}
    try:
        for a in args:
            if labels.get("dtype") is None and hasattr(a, "dtype") \
                    and hasattr(a, "shape"):
                labels["dtype"] = str(a.dtype)
                labels["shape_bucket"] = _shape_bucket(a.shape)
            elif "mesh" not in labels and hasattr(a, "p") and hasattr(a, "q") \
                    and hasattr(a, "mesh"):
                labels["mesh"] = f"{a.p}x{a.q}"
        g = kwargs.get("grid")
        if g is not None and hasattr(g, "p") and hasattr(g, "q"):
            labels["mesh"] = f"{g.p}x{g.q}"
        for k in _LABEL_KWARGS:
            v = kwargs.get(k)
            if v is not None and not hasattr(v, "shape"):
                labels[k] = v
    # slate-lint: disable=SLT501 -- label derivation is best-effort shape/
    # attr inspection of the call's arguments; no computation runs here, and
    # a driver call must never fail because of telemetry
    except Exception:
        pass
    return labels


def _traced(args, kwargs) -> bool:
    """Whether an argument, or the array behind a Matrix wrapper argument, is
    a ``jax.core.Tracer``: the call is being traced, not run."""
    for a in (*args, *kwargs.values()):
        a = getattr(getattr(a, "storage", None), "array", a)
        if isinstance(a, jax.core.Tracer):
            return True
    return False


def instrument(fn=None, *, routine: Optional[str] = None):
    """Decorator: wrap a driver in an observability scope.

    ::

        @instrument
        def getrf_distributed(A, grid, nb=256, ...): ...

    The routine label defaults to the function name.  Works bare or with the
    ``routine=`` override; idempotent on already-instrumented callables.
    """
    def deco(f):
        if getattr(f, INSTRUMENT_ATTR, None):
            return f
        name = routine or f.__name__

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            labels = _derive_labels(args, kwargs)
            if _traced(args, kwargs):
                labels["traced"] = "true"
            with scope(name, **labels):
                return f(*args, **kwargs)

        setattr(wrapper, INSTRUMENT_ATTR, name)
        return wrapper

    return deco(fn) if fn is not None else deco


def on_phases(routine: str, phases: Dict[str, float],
              attempt: Optional[int] = None) -> None:
    """Absorb a driver's phase-timer map into the metrics registry.

    Called lazily by ``utils.trace.record_phases`` so the trace layer stays
    importable without obs.  Each phase becomes one ``slate_phase_seconds``
    histogram sample."""
    hist = REGISTRY.histogram("slate_phase_seconds",
                              "per-phase host wall time (trace.record_phases)")
    for phase, sec in dict(phases).items():
        try:
            labels = {"routine": routine, "phase": str(phase)}
            if attempt is not None:
                labels["attempt"] = str(attempt)
            hist.observe(float(sec), **labels)
        except (TypeError, ValueError):
            continue
