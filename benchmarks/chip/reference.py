"""The plain fp64 reference: a numpy gather join of the generator's own
arrays, the Gram of ``[1, features, label]`` over the joined rows, the
paper's feature scaling and the closed-form ridge solve.

It imports nothing of the program and reads nothing the program made: the
arrays are the generator's, and every join is a lookup of the child's key
in the parent's sorted keys.  Parents must hold each key once (the
foreign-key joins of a star or snowflake); a key that matches no parent
row drops the child row, as a natural join does.
"""

from __future__ import annotations

import numpy as np

CHUNK = 1 << 20  # rows per Gram block: bounded host memory


def _pack(left: list, right: list):
    """Equal int64 codes for equal key tuples on both sides."""
    lk = np.zeros(len(left[0]), np.int64)
    rk = np.zeros(len(right[0]), np.int64)
    for a, b in zip(left, right):
        a, b = a.astype(np.int64), b.astype(np.int64)
        lo = min(a.min(initial=0), b.min(initial=0))
        radix = max(a.max(initial=0), b.max(initial=0)) - lo + 1
        span = max(lk.max(initial=0), rk.max(initial=0)) + 1
        if span > (2**62) // radix:  # re-densify before the product overflows
            _, inv = np.unique(np.concatenate([lk, rk]), return_inverse=True)
            lk, rk = inv[: len(lk)], inv[len(lk) :]
        lk = lk * radix + (a - lo)
        rk = rk * radix + (b - lo)
    return lk, rk


def gather_join(relations: dict, fact: str, joins: list):
    """Row indices of every relation for each fact row, and the mask of
    fact rows that join.  ``joins`` is ``[[child, parent, [attrs]], ...]``
    with each child either the fact or an earlier parent."""
    idx = {fact: np.arange(num_rows(relations[fact]))}
    ok = np.ones(len(idx[fact]), bool)
    for child, parent, attrs in joins:
        crel, prel = relations[child], relations[parent]
        ck, pk = _pack(
            [crel["keys"][a][idx[child]] for a in attrs],
            [prel["keys"][a] for a in attrs],
        )
        order = np.argsort(pk, kind="stable")
        sorted_pk = pk[order]
        if len(sorted_pk) > 1 and (sorted_pk[1:] == sorted_pk[:-1]).any():
            raise ValueError(f"{parent} holds a key {attrs} more than once")
        pos = np.minimum(np.searchsorted(sorted_pk, ck), len(pk) - 1)
        ok &= sorted_pk[pos] == ck
        idx[parent] = order[pos]
    return idx, ok


def num_rows(rel: dict) -> int:
    return len(next(iter(rel["keys"].values())))


def take(rel: dict, rows) -> dict:
    """The rows ``rows`` of a ``{"keys", "values"}`` relation."""
    return {part: {a: c[rows] for a, c in rel[part].items()} for part in ("keys", "values")}


def concat(a: dict, b: dict) -> dict:
    """Relation ``a`` with the rows of ``b`` after its own."""
    return {
        part: {k: np.concatenate([a[part][k], b[part][k]]) for k in a[part]}
        for part in ("keys", "values")
    }


def column(relations: dict, idx: dict, attr: str) -> np.ndarray:
    """``attr`` of each joined row, from the first relation that has it."""
    for name, rows in idx.items():
        rel = relations[name]
        for part in ("keys", "values"):
            if attr in rel[part]:
                return rel[part][attr][rows].astype(np.float64)
    raise KeyError(attr)


def raw_gram(relations: dict, fact: str, joins: list, cols: list):
    """(Σ zᵀz over the joined rows with z = [1, cols], joined row count,
    mask of the fact rows that joined)."""
    idx, ok = gather_join(relations, fact, joins)
    keep = np.flatnonzero(ok)
    idx = {k: v[keep] for k, v in idx.items()}
    g = np.zeros((len(cols) + 1, len(cols) + 1))
    for lo in range(0, len(keep), CHUNK):
        part = {k: v[lo : lo + CHUNK] for k, v in idx.items()}
        z = np.stack(
            [np.ones(len(part[fact]))] + [column(relations, part, c) for c in cols],
            axis=1,
        )
        g += z.T @ z
    return g, int(len(keep)), ok


def moments(relations: dict, attr: str):
    """(Σx, max|x|, count) of ``attr`` over the union of the relations that
    hold it."""
    s, mx, cnt = 0.0, 0.0, 0
    for rel in relations.values():
        for part in ("keys", "values"):
            if attr in rel[part]:
                x = rel[part][attr].astype(np.float64)
                s += float(x.sum())
                mx = max(mx, float(np.abs(x).max(initial=0.0)))
                cnt += len(x)
    return s, mx, cnt


def scale_matrix(mom: dict, feats: list, label: str) -> np.ndarray:
    """A with [1, x_conv, y_conv] = [1, x, y] A: each feature becomes
    (x - avg) / max|x|, the label y - avg (centred, not scaled)."""
    p = len(feats) + 2
    a = np.eye(p)
    for j, col in enumerate(feats + [label], start=1):
        s, mx, cnt = mom[col]
        avg = s / cnt
        div = mx if (mx > 0 and col != label) else 1.0
        a[0, j] = -avg / div
        a[j, j] = 1.0 / div
    return a


def ridge(r: np.ndarray, lam: float) -> np.ndarray:
    """θ of the ridge normal equations with the label's entry pinned to -1."""
    p = r.shape[0]
    t = np.linalg.solve(r[: p - 1, : p - 1] + lam * np.eye(p - 1), r[: p - 1, p - 1])
    return np.concatenate([t, [-1.0]])


class State:
    """One catalog state: the raw Gram, the join count and the per-column
    moments, extended append by append."""

    def __init__(self, gram, count, mom):
        self.gram, self.count, self.mom = gram, count, mom

    def plus(self, gram, count, mom):
        merged = {}
        for c, (s0, m0, n0) in self.mom.items():
            s1, m1, n1 = mom[c]
            merged[c] = (s0 + s1, max(m0, m1), n0 + n1)
        return State(self.gram + gram, self.count + count, merged)


class Reference:
    """The fp64 answer of every catalog state a run reaches: the base
    tables, then each step's appends in order.

    An append may add rows to any relation.  The joins are foreign-key
    lookups, so a fact row joins at most one row of each parent and the
    joined rows of the fact rows already in the join never change: a step
    adds the Gram of its new fact rows and of the earlier fact rows that
    matched no parent before, each joined against the relations as they
    stand after the step."""

    def __init__(self, cfg: dict, relations: dict):
        self.feats, self.label = list(cfg["features"]), cfg["label"]
        self.fact, self.joins, self.lam = cfg["fact"], cfg["joins"], cfg["ridge"]
        self.relations = dict(relations)
        cols = self.feats + [self.label]
        gram, count, ok = raw_gram(self.relations, self.fact, self.joins, cols)
        self.unjoined = take(self.relations[self.fact], ~ok)
        mom = {c: moments(self.relations, c) for c in cols}
        self.states = [State(gram, count, mom)]

    def append(self, batch: dict) -> None:
        """Add the state after one step's appends, ``{relation: rows}``."""
        cols = self.feats + [self.label]
        for name, rows in batch.items():
            if name != self.fact:
                self.relations[name] = concat(self.relations[name], rows)
        fact = self.unjoined
        if self.fact in batch:
            fact = concat(fact, batch[self.fact])
        rels = {**self.relations, self.fact: fact}
        gram, count, ok = raw_gram(rels, self.fact, self.joins, cols)
        self.unjoined = take(fact, ~ok)
        mom = {c: moments(batch, c) for c in cols}
        self.states.append(self.states[-1].plus(gram, count, mom))

    def scaled(self, state: State, feats: list):
        """(scaled Gram R, scale matrix A, reference θ in original units) of
        the ridge problem over ``feats`` (any subset of the configuration's
        features, in any order) at ``state``."""
        idx = [0] + [1 + self.feats.index(f) for f in feats] + [len(self.feats) + 1]
        a = scale_matrix(state.mom, list(feats), self.label)
        r = a.T @ state.gram[np.ix_(idx, idx)] @ a
        theta_conv = ridge(r, self.lam)
        return r, a, a @ theta_conv


def fitted_gap(theta: np.ndarray, theta_ref: np.ndarray, r: np.ndarray, a: np.ndarray) -> float:
    """How far the fitted values of ``theta`` lie from the reference's over
    the joined rows, relative to the label's spread: ||Z (θ - θ_ref)|| /
    ||y - ȳ||, in the scaled coordinates of ``r`` (Z θ = Z_conv A⁻¹ θ)."""
    d = np.linalg.solve(a, np.asarray(theta, np.float64) - theta_ref)[:-1]
    t = slice(0, r.shape[0] - 1)
    spread = r[-1, -1] - r[0, -1] ** 2 / r[0, 0]
    return float(np.sqrt(max(d @ r[t, t] @ d, 0.0) / spread))
