"""The program's one tracing system: layer scopes, host spans, counters and
the JIT record (DESIGN.md §17).

  * ``scope(name)`` is a ``jax.named_scope`` over the fixed vocabulary
    ``SCOPES``: every op traced inside it carries the name in its HLO
    ``op_name`` metadata, so a device op of the profiler's trace belongs to
    the innermost ``simplex.`` component of that path. Scopes add metadata
    only; the compiled program is unchanged.
  * ``span(name, **attrs)`` marks a host-side region. It always opens a
    ``jax.profiler.TraceAnnotation("simplex.<name>")``, which lands on the
    profiler's host plane, on the clock of the device events. While a
    ``record()`` context is active it also keeps a ``Span`` (id, parent id,
    name, ``time.perf_counter`` start and end, attributes) in memory; with
    no record active nothing is kept.
  * ``count(name, k)`` / ``counter(name)``: trace-level counters. Bumped
    from Python code that runs once per trace under ``jit``, a counter
    counts what is baked into a compiled program (lattice builds per step,
    MVMs and their RHS columns per CG iteration, embeds per query batch).
  * ``jit_events()``: every trace, lowering and backend compile JAX
    reported since import, from one ``jax.monitoring`` listener registered
    here, as ``JitEvent(kind, fun_name, t0, t1)`` on ``time.perf_counter``.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

PREFIX = "simplex."

# device scopes, outermost first within each layer
SCOPES = tuple(PREFIX + s for s in (
    "build", "build.embed", "build.dedup", "build.neighbours",
    "build.splat_plan",
    "mvm", "mvm.splat", "mvm.blur", "mvm.slice", "mvm.psum",
    "cg", "lanczos", "slq", "precond",
    "grad", "posterior.cross", "posterior.var", "serve.slice"))

# host spans, by the module that opens them
SPANS = tuple(PREFIX + s for s in (
    # gp/train.fit
    "epoch", "step", "step.read", "validation", "regrow", "rollback",
    "checkpoint",
    # core/lattice.build_lattice_auto
    "build_auto",
    # launch/serve_gp.GPServeEngine
    "query", "query.wait", "query.predict", "query.check",
    "refresh", "refresh.freeze", "refresh.validate", "refresh.persist"))

COUNTERS = ("lattice.builds", "lattice.embeds", "mvm.calls", "mvm.cols")

SPAN_LIMIT = 1 << 20  # spans one record keeps; later ones are dropped
JIT_LIMIT = 1 << 16  # newest JIT events kept

JIT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def scope(name: str):
    """Name the device ops traced inside: ``simplex.<name>``."""
    full = PREFIX + name
    if full not in SCOPES:
        raise ValueError(f"{full!r} is not in obs.SCOPES")
    return jax.named_scope(full)


# -- host spans ---------------------------------------------------------------


class Span(NamedTuple):
    id: int
    parent_id: int | None
    name: str  # without the prefix
    t0: float  # time.perf_counter()
    t1: float
    attrs: dict


_record: list | None = None
_ids = itertools.count(1)
_open = threading.local()  # per thread: ids of the spans open on it


@contextlib.contextmanager
def span(name: str, **attrs):
    """Host span ``simplex.<name>``; yields its ``attrs`` dict, which the
    body may add to (e.g. a count known only at the end)."""
    full = PREFIX + name
    if full not in SPANS:
        raise ValueError(f"{full!r} is not in obs.SPANS")
    with jax.profiler.TraceAnnotation(full):
        rec = _record
        if rec is None:
            yield attrs
            return
        stack = getattr(_open, "ids", None)
        if stack is None:
            stack = _open.ids = []
        sid = next(_ids)
        parent = stack[-1] if stack else None
        stack.append(sid)
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            stack.pop()
            if len(rec) < SPAN_LIMIT:
                rec.append(Span(sid, parent, name, t0, t1, attrs))


@contextlib.contextmanager
def record():
    """Keep the spans closed inside, from every thread; yields their list
    (in closing order)."""
    global _record
    prev, _record = _record, []
    try:
        yield _record
    finally:
        _record = prev


# -- counters -----------------------------------------------------------------

_counters = collections.Counter()


def count(name: str, k: int = 1) -> None:
    if name not in COUNTERS:
        raise ValueError(f"{name!r} is not in obs.COUNTERS")
    _counters[name] += k


def counter(name: str) -> int:
    """Total of ``name`` since import (trace-level under jit)."""
    if name not in COUNTERS:
        raise ValueError(f"{name!r} is not in obs.COUNTERS")
    return _counters[name]


# -- JIT record ---------------------------------------------------------------


class JitEvent(NamedTuple):
    kind: str  # "trace", "lower" or "compile"
    fun_name: str
    t0: float  # time.perf_counter(), t1 - duration
    t1: float


_jit = collections.deque(maxlen=JIT_LIMIT)


def _on_duration(event: str, duration: float, **kw) -> None:
    kind = JIT_KINDS.get(event)
    if kind is not None:
        t1 = time.perf_counter()
        _jit.append(JitEvent(kind, str(kw.get("fun_name", "")),
                             t1 - duration, t1))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def jit_events() -> list[JitEvent]:
    """The newest ``JIT_LIMIT`` trace, lowering and compile events. Traces
    nest (a function's trace holds those of the jitted functions it calls),
    so their durations overlap."""
    return list(_jit)
