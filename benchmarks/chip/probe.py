"""Benchmark-side hooks into the program: spans around the calls into each
layer, the shapes of every node step, and what each train returned.

Nothing here changes what the program computes.  Each hook wraps a
function of the program (a module attribute or a method), calls the
original and records around it; :meth:`Hooks.close` puts every original
back.  Span names are the benchmark's own and stay fixed whatever the
program's internals are called.
"""

from __future__ import annotations

import functools
import threading

from jax.profiler import TraceAnnotation

#: span name -> (module path, attribute path) of the wrapped callable
SPANS = {
    "service.cycle": ("repro.serve.factorized", "FactorizedService._drain_cycle"),
    "service.finish": ("repro.serve.factorized", "FactorizedService._finish"),
    "store.append": ("repro.core.store", "Store.append"),
    "store.flush": ("repro.core.store", "Store.flush"),
    "engine.init": ("repro.core.factorize", "FactorizedEngine.__init__"),
    "engine.run_batch": ("repro.core.factorize", "FactorizedEngine.run_batch"),
    "relation.group_key": ("repro.core.factorize", "group_key"),
    "relation.sort_merge_join": ("repro.core.factorize", "sort_merge_join"),
    "ops.group_ids_device": ("repro.kernels.ops", "group_ids_device"),
    "ops.segment_view": ("repro.kernels.ops", "segment_view"),
    "ops.segment_blocks": ("repro.kernels.ops", "segment_blocks"),
}
#: the node steps whose shapes feed ``work.py``
NODE_SPANS = ("ops.segment_view", "ops.segment_blocks")


def _resolve(module: str, path: str):
    import importlib

    owner = importlib.import_module(module)
    *parents, name = path.split(".")
    for p in parents:
        owner = getattr(owner, p)
    return owner, name


def node_shape(span: str, args, kw) -> dict:
    """(rows M, view width k, degree, groups G) of one node step, from the
    arguments of ``ops.segment_view(c, x, l, q, seg, G)`` or
    ``ops.segment_blocks(c, l, q, seg, G)``."""
    degree = kw.get("degree", 2)
    if span == "ops.segment_view":
        c, _, l = args[0], args[1], args[2]
        groups = args[5] if len(args) > 5 else kw["num_groups"]
    else:
        c, l = args[0], args[1]
        groups = args[4] if len(args) > 4 else kw["num_groups"]
    k = int(l.shape[1]) if (l is not None and degree >= 1) else 0
    return {"span": span, "rows": int(c.shape[0]), "k": k,
            "degree": int(degree), "groups": int(groups)}


class Hooks:
    """Installed hooks; ``close()`` restores the program."""

    def __init__(self):
        self._orig = []
        self.lock = threading.Lock()
        self.nodes = []  # node-step shapes, in call order
        self.calls = {}  # span -> calls
        self.counts = {}  # id(ticket) -> joined rows a read counted
        self.engines = []  # (use_node_kernels, device_grouping) per engine

    def _patch(self, module: str, path: str, make):
        owner, name = _resolve(module, path)
        fn = getattr(owner, name)
        self._orig.append((owner, name, fn))
        setattr(owner, name, functools.wraps(fn)(make(fn)))

    def spans(self) -> "Hooks":
        """Wrap every call in ``SPANS`` in a profiler span of that name,
        and record node-step shapes."""
        for span, (module, path) in SPANS.items():

            def make(fn, span=span):
                def wrapped(*args, **kw):
                    with self.lock:
                        self.calls[span] = self.calls.get(span, 0) + 1
                        if span in NODE_SPANS:
                            self.nodes.append(node_shape(span, args, kw))
                    with TraceAnnotation(span):
                        return fn(*args, **kw)

                return wrapped

            self._patch(module, path, make)
        return self

    def results(self, service) -> "Hooks":
        """Record the count behind every read ``service`` serves, whatever
        its kind: the count of its batch's ungrouped block (no group keys,
        one group; a train's ``"cof"``), the joined rows it read.  Record
        too the kernel flags of every engine built."""

        def finish(fn):
            def wrapped(read, blocks):
                whole = [b for b in blocks.values() if not b.keys and b.num_groups == 1]
                if whole:
                    with self.lock:
                        self.counts[id(read.ticket)] = float(whole[0].count[0])
                return fn(read, blocks)

            return wrapped

        orig = service._finish
        service._finish = functools.wraps(orig)(finish(orig))
        self._orig.append((service, "_finish", None))

        def init(fn):
            def wrapped(engine, *args, **kw):
                fn(engine, *args, **kw)
                with self.lock:
                    self.engines.append(
                        (engine.use_node_kernels, engine.device_grouping)
                    )

            return wrapped

        self._patch("repro.core.factorize", "FactorizedEngine.__init__", init)
        return self

    def close(self) -> None:
        for owner, name, fn in reversed(self._orig):
            if fn is None:
                delattr(owner, name)
            else:
                setattr(owner, name, fn)
        self._orig.clear()
