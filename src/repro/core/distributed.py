"""Distributed cofactor computation — the paper's algebra as the mesh plan.

Proposition 4.1's *commutativity with union* — partition the data, compute
per-partition cofactors, sum — **is** data parallelism.  This module maps it
onto a JAX device mesh:

* each ``data``-axis shard holds a horizontal partition of the (largest)
  fact relation plus replicas of the small dimension relations — the layout
  a distributed in-memory DBMS would choose;
* every shard runs the same Gram/cofactor computation on its rows;
* one ``psum`` over the ``data`` axis (and ``pod`` axis when present)
  produces the global cofactor matrix.  The matrix is tiny (p×p, p = #feats
  + 2), so the collective is latency- not bandwidth-bound.

``sharded_gram`` is the shard_map building block; ``sharded_cofactors``
applies it to a partitioned design matrix.  ``partitioned_cofactors_host``
demonstrates the same algebra without a mesh (host-side partition + sum) and
is used by tests as the oracle.

Incremental maintenance composes with the same algebra: an *append* of new
rows Δ is a union, so ``incremental_sharded_cofactors`` computes the delta
cofactors of Δ per shard (one psum) and folds them into the previous global
cofactors with ``Cofactors.__add__`` — no rescan of the historical data.

View-cache independence: the sharded paths consume already-extracted
arrays, so they are agnostic to the store's persistent per-node view cache
— results are bit-identical with the cache on or off (tested in
``tests/test_sharding.py``).  The two maintenance schemes agree by
Prop. 4.1: a store whose caches were delta-maintained under ``append`` and
a sharded fold of the same delta arrays land on the same cofactors, which
is what lets a mesh fold the deltas while the store keeps the factorized
views warm for the next retrain.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .categorical import CatCofactors, SparseCounts, cat_cofactors_from_arrays
from .factorize import Cofactors

__all__ = [
    "sharded_gram",
    "sharded_cofactors",
    "sharded_cat_cofactors",
    "partitioned_cofactors_host",
    "incremental_sharded_cofactors",
    "incremental_sharded_cat_cofactors",
]


ROW_BLOCK = 4096  # rows per f32 partial sum in _dot_t


def _dot_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``aᵀ @ b`` over the rows of one shard, in f32.  The products take
    ``Precision.HIGHEST`` (a TPU otherwise multiplies f32 operands in one
    bf16 pass, about three significant digits), and the rows are summed in
    blocks of ``ROW_BLOCK`` whose partials are added at the end: a single
    accumulator running down millions of rows loses digits with every
    addition.  Zero rows pad the last block; they add nothing."""
    rows = a.shape[0]
    if rows <= ROW_BLOCK:
        return jnp.matmul(a.T, b, precision=jax.lax.Precision.HIGHEST)
    pad = ((0, (-rows) % ROW_BLOCK), (0, 0))
    a = jnp.pad(a, pad).reshape(-1, ROW_BLOCK, a.shape[1])
    b = jnp.pad(b, pad).reshape(-1, ROW_BLOCK, b.shape[1])
    parts = jnp.einsum(
        "nrk,nrl->nkl", a, b, precision=jax.lax.Precision.HIGHEST
    )
    return parts.sum(axis=0)


def _gram_local(z: jnp.ndarray) -> jnp.ndarray:
    """Local Gram of one shard; fp32 accumulation."""
    return _dot_t(z, z)


def sharded_gram(z: jnp.ndarray, mesh: Mesh, data_axes: Sequence[str]):
    """Global Gram Z^T Z with rows sharded over ``data_axes`` of ``mesh``.

    The per-shard Gram is followed by a single psum — the paper's
    union-commutativity, executed as a collective.
    """
    axes = tuple(data_axes)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(axes, None),
        out_specs=P(),  # replicated result
    )
    def _fn(z_local):
        return jax.lax.psum(_gram_local(z_local), axes)

    return _fn(z)


def sharded_cofactors(
    z: np.ndarray,
    features: Sequence[str],
    mesh: Mesh,
    data_axes: Sequence[str] = ("data",),
) -> Cofactors:
    """Cofactors of a design matrix ``z`` (WITHOUT intercept column) sharded
    over the mesh's data axes.  Pads rows with zeros to a shard multiple —
    zero rows contribute nothing to any cofactor (union with empty data)."""
    nshards = 1
    for a in data_axes:
        nshards *= mesh.shape[a]
    m, k = z.shape
    pad = (-m) % nshards
    if pad:
        z = np.concatenate([z, np.zeros((pad, k), dtype=z.dtype)], axis=0)
    # prepend the intercept column: zeros on padded rows would corrupt the
    # count, so build it explicitly with the true-row indicator.
    ones = np.concatenate([np.ones((m,)), np.zeros((pad,))])[:, None]
    zz = np.concatenate([ones, z], axis=1).astype(np.float32)
    sharding = NamedSharding(mesh, P(tuple(data_axes), None))
    zz_dev = jax.device_put(jnp.asarray(zz), sharding)
    gram = np.asarray(sharded_gram(zz_dev, mesh, data_axes), dtype=np.float64)
    return Cofactors(
        count=float(gram[0, 0]),
        lin=gram[0, 1:],
        quad=gram[1:, 1:],
        features=list(features),
    )


def incremental_sharded_cofactors(
    base: Cofactors,
    z_delta: np.ndarray,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
) -> Cofactors:
    """Fold an appended row batch into existing global cofactors.

    ``base`` holds the cofactors of all rows seen so far; ``z_delta`` is the
    design matrix (WITHOUT intercept column) of the newly appended rows only.
    The delta cofactors are computed over the mesh when one is given (each
    shard sees a horizontal slice of Δ, one psum reduces them) and on the
    host otherwise; union commutativity makes ``base + delta`` exact.

    Precision: the mesh path accumulates each delta in fp32 on-device
    (~1e-7 relative per delta), so its rounding flows into the long-lived
    base — the host path (``mesh=None``) is fp64 and matches the fp64
    maintenance policy of ``Store.append``.  Prefer the host path for
    accumulators that must survive many appends; use the mesh path when
    delta volume, not accumulation lifetime, is the bottleneck.
    """
    if z_delta.shape[0] == 0:
        return base
    if mesh is None:
        delta = partitioned_cofactors_host(z_delta, base.features, 1)
    else:
        delta = sharded_cofactors(z_delta, base.features, mesh, data_axes)
    return base + delta


def sharded_cat_cofactors(
    x_cont: np.ndarray,
    cat_ids: np.ndarray,
    cont: Sequence[str],
    cat: Sequence[str],
    domains: dict,
    mesh: Mesh,
    data_axes: Sequence[str] = ("data",),
    fd=None,  # Optional[repro.core.fd.FDReduction]
) -> CatCofactors:
    """Categorical cofactors with rows sharded over the mesh's data axes.

    ``fd`` (an ``FDReduction`` over ``cat``) drops functionally-determined
    attributes *before* the multi-hot block is built: the concatenated
    one-hot width shrinks from Σ D_all to Σ D_kept, shrinking both fused
    matmuls and all three psums.  The result then covers only the kept
    attributes — expand with ``repro.core.fd.expand_cat_cofactors`` when
    the full blocks are needed.

    Same union-commutativity as ``sharded_cofactors``, extended to the
    grouped blocks: every shard builds ONE concatenated multi-hot block
    H = [onehot(c₁) | … | onehot(c_n)] over its local rows (a [rows, ΣD]
    *shard* slice, never the global design matrix) and evaluates the whole
    categorical batch with two fused matmuls — H^T·[1|x] carries every
    per-category count/Σx block and H^T·H every cat×cat co-occurrence
    block — mirroring the engine's single-pass multi-output plan.  Three
    psums total (Gram, H^T·u, H^T·H) reduce the shards, independent of
    |cat|, where the pre-fusion formulation paid one matmul + psum per
    attribute plus one per pair.  Rows are padded to a shard multiple with
    id −1 — an all-zero one-hot row — so padding contributes nothing,
    mirroring the kernel's out-of-range trick.
    """
    cont, cat = list(cont), list(cat)
    if fd is not None and fd.dropped:
        kept_idx = [cat.index(c) for c in fd.kept]
        return sharded_cat_cofactors(
            x_cont,
            cat_ids[:, kept_idx],
            cont,
            list(fd.kept),
            {c: domains[c] for c in fd.kept},
            mesh,
            data_axes,
        )
    axes = tuple(data_axes)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    m, k = x_cont.shape
    pad = (-m) % nshards
    ind = np.concatenate([np.ones(m), np.zeros(pad)])[:, None]
    xz = np.concatenate([x_cont, np.zeros((pad, k))], axis=0)
    u = np.concatenate([ind, xz], axis=1).astype(np.float32)
    for i, c in enumerate(cat):
        if len(cat_ids) == 0:
            continue
        lo, hi = int(cat_ids[:, i].min()), int(cat_ids[:, i].max())
        if lo < 0 or hi >= int(domains[c]):
            raise ValueError(
                f"category ids of {c!r} span [{lo}, {hi}], outside domain "
                f"[0, {int(domains[c])}) — out-of-range one-hot rows are "
                "all zeros and would be silently dropped (negative ids are "
                "reserved for internal shard padding)"
            )
    ids = np.concatenate(
        [cat_ids, np.full((pad, len(cat)), -1)], axis=0
    ).astype(np.int32)
    doms = [int(domains[c]) for c in cat]
    offs = np.concatenate([[0], np.cumsum(doms)]).astype(int)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None)),
        out_specs=(P(), P(), P()),
    )
    def _fn(u_local, ids_local):
        rows = u_local.shape[0]
        hot = jnp.concatenate(
            [
                (
                    ids_local[:, i, None]
                    == jax.lax.broadcasted_iota(jnp.int32, (rows, d), 1)
                ).astype(jnp.float32)
                for i, d in enumerate(doms)
            ],
            axis=1,
        )  # [rows, ΣD] — n_cat ones per (unpadded) row
        gram = _dot_t(u_local, u_local)
        hu = _dot_t(hot, u_local)  # every [D_c, 1+k] block, one matmul
        hh = _dot_t(hot, hot)  # every cat×cat block, one matmul
        return (
            jax.lax.psum(gram, axes),
            jax.lax.psum(hu, axes),
            jax.lax.psum(hh, axes),
        )

    sharding = NamedSharding(mesh, P(axes, None))
    gram, hu, hh = _fn(
        jax.device_put(jnp.asarray(u), sharding),
        jax.device_put(jnp.asarray(ids), sharding),
    )
    gram = np.asarray(gram, dtype=np.float64)
    hu = np.asarray(hu, dtype=np.float64)
    hh = np.asarray(hh, dtype=np.float64)
    cat_count = {c: hu[offs[i] : offs[i + 1], 0] for i, c in enumerate(cat)}
    cat_cont = {c: hu[offs[i] : offs[i + 1], 1:] for i, c in enumerate(cat)}
    cat_cat = {}
    for i in range(len(cat)):
        for j in range(i + 1, len(cat)):
            cat_cat[(cat[i], cat[j])] = SparseCounts.from_dense(
                hh[offs[i] : offs[i + 1], offs[j] : offs[j + 1]]
            )
    return CatCofactors(
        count=float(gram[0, 0]),
        lin=gram[0, 1:],
        quad=gram[1:, 1:],
        cont=cont,
        cat=cat,
        domains={c: int(domains[c]) for c in cat},
        cat_count=cat_count,
        cat_cont=cat_cont,
        cat_cat=cat_cat,
    )


def incremental_sharded_cat_cofactors(
    base: CatCofactors,
    x_delta: np.ndarray,
    ids_delta: np.ndarray,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
) -> CatCofactors:
    """Fold appended rows into existing categorical cofactors — the
    categorical twin of ``incremental_sharded_cofactors`` (same precision
    trade-off: mesh path accumulates fp32, host path fp64).  Unseen
    category ids in the delta grow the domains: the delta blocks are built
    at the grown size and ``__add__`` zero-pads ``base`` up to match."""
    if x_delta.shape[0] == 0:
        return base
    domains = {
        c: max(base.domains[c], int(ids_delta[:, i].max()) + 1)
        for i, c in enumerate(base.cat)
    }
    if mesh is None:
        delta = cat_cofactors_from_arrays(
            x_delta, ids_delta, base.cont, base.cat, domains
        )
    else:
        delta = sharded_cat_cofactors(
            x_delta, ids_delta, base.cont, base.cat, domains,
            mesh, data_axes,
        )
    return base + delta


def partitioned_cofactors_host(
    z: np.ndarray, features: Sequence[str], num_parts: int
) -> Cofactors:
    """Host-side demonstration of union commutativity (test oracle)."""
    parts = np.array_split(z, num_parts, axis=0)
    out: Optional[Cofactors] = None
    for part in parts:
        ones = np.ones((part.shape[0],))
        cof = Cofactors(
            count=float(part.shape[0]),
            lin=part.T @ ones,
            quad=part.T @ part,
            features=list(features),
        )
        out = cof if out is None else out + cof
    assert out is not None
    return out
