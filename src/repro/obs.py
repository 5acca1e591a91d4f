"""Spans and process counters: where the program's time and copies go.

One mechanism, read two ways.  :class:`span` opens a
``jax.profiler.TraceAnnotation``, so a span lands in the profiler's own
trace, on the clock of the device operations it launches, with its
attributes as trace stats under the bare span name.  The same span adds
its host self time (its duration less that of the spans opened inside it
on the same thread) to a process counter, so the host side can be read
without a trace too.

With no trace being recorded a span costs one ``TraceAnnotation``, two
clock reads, a push and a pop on a thread-local stack and one counter
update: no formatting, nothing that grows with the data, no device sync.
Spans wrap calls; none sits inside a jitted function.

The counters cover what no ``Store`` owns:

- ``h2d_bytes`` / ``d2h_bytes``: bytes copied by :func:`to_device` /
  :func:`to_host`, the only counted copy sites;
- ``dispatches``: calls of each node kernel and of device grouping;
- ``group_rows_device`` / ``group_rows_host``: rows the engine grouped
  with the GROUP BY key packed on the device / by the host's
  ``group_key`` (:func:`grouped`);
- ``lowered``: programs lowered (a shape new to the process), by the
  innermost span open on the lowering thread (``none`` outside any);
- ``span_self_s``: host self seconds by span name.

:func:`snapshot` returns them as plain data; ``FactorizedService.
cache_info()["process"]`` reports it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

__all__ = [
    "SPANS", "span", "to_device", "to_host", "dispatch", "grouped", "snapshot",
]

#: every span the program opens, with what it covers
SPANS: Dict[str, str] = {
    "repro.service.submit": "a request's admission on the client thread "
    "(attrs request, kind, tenant)",
    "repro.service.cycle": "one drain cycle: pop, batch groups, writes, "
    "idle fold (attrs reads, writes)",
    "repro.service.batch": "one coalesced traversal: engine build, "
    "run_batch, scatter (attrs requests)",
    "repro.service.solve": "one request's post-processing: rescale and "
    "closed-form solve (attrs request)",
    "repro.store.append": "Store.append: validate, concat, encode, log "
    "(attrs relation, rows)",
    "repro.store.fold": "Store._drain_all: fold pending deltas into the "
    "caches (attrs relation, rows)",
    "repro.engine.init": "FactorizedEngine construction: read barrier "
    "and attribute encoding",
    "repro.engine.node": "one node evaluated by the executor, children "
    "included (attrs node, degree)",
    "repro.engine.join": "join keys and sort-merge join of two views "
    "(attrs rows_left, rows_right)",
    "repro.engine.gather": "key-column gathers and block takes of a join "
    "(attrs rows)",
    "repro.engine.feature": "host gather and upload of a node's feature "
    "values (attrs rows)",
    "repro.engine.group": "a GROUP BY's ids and surviving key columns "
    "(attrs rows)",
    "repro.engine.group_key": "the GROUP BY key's host work: the int64 "
    "code (host grouping) or the word layout and column padding (device "
    "grouping) (attrs rows)",
    "repro.kernel.group_ids": "device grouping: key column upload, word "
    "packing, sort passes, run detection, order/start pull (attrs rows)",
    "repro.kernel.segment_view": "one fused extend + GROUP BY node step "
    "(attrs rows, k, degree, groups)",
    "repro.kernel.segment_blocks": "one multi-block GROUP BY node step "
    "(attrs rows, k, degree, groups)",
    "repro.kernel.pack": "sorting and packing a node step's rows for the "
    "staircase kernel (attrs rows, width)",
}

#: the lowering event counted in ``lowered``
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

# leaf lock over every counter below: nothing is acquired under it
_counter_lock = threading.Lock()
_bytes = {"h2d_bytes": 0, "d2h_bytes": 0}
_dispatches: Dict[str, int] = {}
_grouped = {"group_rows_device": 0, "group_rows_host": 0}
_lowered: Dict[str, int] = {}
_span_ns: Dict[str, int] = {}
_local = threading.local()


def _stack() -> list:
    """This thread's open spans, innermost last."""
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _stats(attrs: dict) -> dict:
    """Attributes as trace stats: a sequence becomes its comma-joined items."""
    return {
        k: ",".join(map(str, v)) if isinstance(v, (tuple, list)) else v
        for k, v in attrs.items()
    }


class span:
    """``with span("repro.<layer>.<step>", **attrs) as sp:`` — a profiler
    span named ``name`` carrying ``attrs`` (small ints and strings the
    caller already has; a tuple of ints is joined with commas), formatted
    only while a trace is recorded.  ``sp.set(**attrs)`` adds attributes
    known only inside the span."""

    __slots__ = ("name", "_attrs", "_me", "_t0", "_child")

    def __init__(self, name: str, **attrs) -> None:
        self.name = name
        self._attrs = attrs

    def __enter__(self) -> "span":
        if self._attrs and TraceAnnotation.is_enabled():
            self._me = TraceAnnotation(self.name, **_stats(self._attrs))
        else:
            self._me = TraceAnnotation(self.name)
        self._me.__enter__()
        _stack().append(self)
        self._child = 0
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        if TraceAnnotation.is_enabled():
            self._me.set_metadata(**_stats(attrs))

    def __exit__(self, *exc) -> None:
        took = time.perf_counter_ns() - self._t0
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1]._child += took
        with _counter_lock:
            _span_ns[self.name] = _span_ns.get(self.name, 0) + took - self._child
        self._me.__exit__(*exc)


def to_device(x, dtype=None):
    """``jnp.asarray(x, dtype)``, counting the bytes uploaded; a jax array
    passes through uncounted."""
    out = jnp.asarray(x, dtype=dtype)
    if not isinstance(x, jax.Array):
        with _counter_lock:
            _bytes["h2d_bytes"] += out.nbytes
    return out


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counting the bytes a jax array brings
    back; a host array passes through uncounted."""
    out = np.asarray(x, dtype=dtype)
    if isinstance(x, jax.Array):
        with _counter_lock:
            _bytes["d2h_bytes"] += x.nbytes
    return out


def dispatch(kernel: str) -> None:
    """Count one call of ``kernel``."""
    with _counter_lock:
        _dispatches[kernel] = _dispatches.get(kernel, 0) + 1


def grouped(where: str, rows: int) -> None:
    """Count ``rows`` grouped with the key packed on the ``"device"`` or
    the ``"host"``."""
    with _counter_lock:
        _grouped[f"group_rows_{where}"] += rows


def _on_duration(event: str, secs: float, **kw) -> None:
    if event != _LOWER:
        return
    stack = _stack()
    where = stack[-1].name if stack else "none"
    with _counter_lock:
        _lowered[where] = _lowered.get(where, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    """The process counters as plain data (totals since import)."""
    with _counter_lock:
        return {
            **_bytes,
            **_grouped,
            "dispatches": dict(_dispatches),
            "lowered": dict(_lowered),
            "span_self_s": {k: v * 1e-9 for k, v in _span_ns.items()},
        }
