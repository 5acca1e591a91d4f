"""The closed-loop driver: clients that each send a request only after
the last one returned, over one ``FactorizedService``.

A mix (``mixes/<name>.json``) that names ``"driver": "closed_loop"`` is
data for this file:

- ``clients``: closed-loop clients, each sending its next request only
  after the last one returned;
- ``feature_sets``: the feature list of each client's trains, by index
  modulo their number (absent: the configuration's own features);
- ``appends_per_train``: batches of the configuration's generator
  (``new_rows``: rows for one or more relations) each client sends before
  every train, each relation's append awaited until acknowledged;
- ``view_cache_bytes``: the store's view-cache budget (0 turns it off);
- ``warm_steps``: steps each client runs in set-up, before the window.

A step is the appends and then the train.  A retrain is timed from the
submission of its first append to the θ of the train that follows it.
A later kind of loop is a driver file of its own beside this one, with
the same ``Driver`` interface.
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

TIMEOUT_S = 600.0  # an answer that takes longer than this never came


class Driver:
    """Closed-loop clients over one service; every request is recorded."""

    def __init__(self, service, mix: dict, cfg: dict, data: dict, generator,
                 vorder, hooks, relation_of):
        self.svc, self.mix, self.cfg, self.data = service, mix, cfg, data
        self.generator, self.vorder, self.hooks = generator, vorder, hooks
        self.relation_of = relation_of  # arrays -> the program's Relation
        self.appended = []  # {relation: rows} batches in acknowledgement order
        self._lock = threading.Lock()
        self._append_lock = threading.Lock()

    def features(self, client: int) -> list:
        sets = self.mix.get("feature_sets") or [self.cfg["features"]]
        return list(sets[client % len(sets)])

    def step(self, client: int) -> dict:
        """One step of ``client``: its appends, each acknowledged, then its
        train.  Returns the step's record; a request that fails or never
        comes ends the step with ``ok`` false."""
        rec = {"client": client, "appends": [], "ok": False}
        try:
            self._step(client, rec)
            rec["ok"] = True
        except Exception as err:  # the run goes on; the record says why
            rec["error"] = repr(err)
            rec.setdefault("done", time.perf_counter())
        return rec

    def _step(self, client: int, rec: dict) -> None:
        tenant = f"client-{client}"
        for _ in range(self.mix.get("appends_per_train", 0)):
            with self._append_lock:  # draws in the order they are acknowledged
                batch = self.generator.new_rows(self.cfg, self.data)
                for name, rows in batch.items():
                    delta = self.relation_of(name, rows)
                    t0 = time.perf_counter()
                    with TraceAnnotation("client.append"):
                        self.svc.append(tenant, name, delta).result(timeout=TIMEOUT_S)
                    rec["appends"].append((t0, time.perf_counter()))
                self.appended.append(batch)
        with self._append_lock:
            rec["state"] = len(self.appended)  # the appends it must read
        rec["submit"] = time.perf_counter()
        rec["start"] = rec["appends"][0][0] if rec["appends"] else rec["submit"]
        with TraceAnnotation("client.train"):
            ticket = self.svc.train(
                tenant, self.vorder, self.features(client), self.cfg["label"],
                ridge=self.cfg["ridge"],
            )
            res = ticket.result(timeout=TIMEOUT_S)
        rec["done"] = time.perf_counter()
        rec["theta"] = res.theta
        rec["features"] = res.features
        rec["count"] = self.hooks.counts.pop(id(ticket), None)

    def run(self, steps: int | None = None, seconds: float | None = None):
        """Each client runs ``steps`` steps, or steps started until
        ``seconds`` have passed (the last runs to its end).  Returns the
        records of this call, in completion order."""
        t_end = None if seconds is None else time.perf_counter() + seconds
        out = []

        def client(i):
            n = 0
            while (steps is None or n < steps) and (
                t_end is None or time.perf_counter() < t_end
            ):
                rec = self.step(i)
                with self._lock:
                    out.append(rec)
                n += 1
                if not rec["ok"]:
                    return

        threads = [
            threading.Thread(target=client, args=(i,), name=f"client-{i}")
            for i in range(self.mix["clients"])
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out
