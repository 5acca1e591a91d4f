"""Cofactor matrices: factorized vs. materialized ("noPre") paths (paper §3.4).

Three engines, mirroring the paper's evaluation matrix:

* ``cofactors_factorized`` (re-exported)  — one pass over the factorized join
  (the paper's ``fact`` versions), O(factorization size).
* ``cofactors_materialized``              — flat join then Gram matrix
  X^T X (the ``noPre`` baseline), O(|D|^rho*); accelerated by the Pallas
  ``gram`` kernel when ``use_kernel=True``.
* ``cofactors_row_engine``                — row-at-a-time interpreted loop,
  the *disk-row-engine proxy* standing in for PostgreSQL in the
  engine-comparison benchmark (Fig. 9 analogue).  Never used for training.

Streaming / incremental paths (union commutativity, Prop. 4.1):

* ``cofactors_streaming``  — accumulates X^T X chunk-by-chunk through the
  Pallas ``gram`` kernel and folds the per-chunk ``Cofactors`` with
  ``__add__``, so arbitrarily large design matrices never materialize on
  device at once.  ``cofactors_materialized(..., chunk_rows=N)`` routes the
  noPre path through it.  Accepts any iterable of [m_i, k] row chunks, so
  it also serves out-of-core / append-stream sources directly.
* ``cofactors_grouped``    — per-group cofactors of a partition labeling in
  ONE fused pass via the Pallas ``segment_gram`` kernel (u = [1, x] makes
  u·u^T carry count/lin/quad together); the groups sum back to the global
  cofactors with ``__add__`` — the same algebra ``Store.append`` and the
  distributed reduction use.

Categorical features (AC/DC-style sparse group-by blocks instead of
one-hot columns) live in ``repro.core.categorical``; GLMs over the
compressed join in ``repro.core.glm``.  Both build on the same grouped
aggregates — ``FactorizedEngine(group_by=...)`` on the factorized side,
``segment_gram`` on the materialized side.
"""

from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .factorize import Cofactors, cofactors_factorized
from .relation import Relation
from .store import Store

__all__ = [
    "Cofactors",
    "cofactors_factorized",
    "cofactors_materialized",
    "cofactors_from_matrix",
    "cofactors_grouped",
    "cofactors_row_engine",
    "cofactors_streaming",
    "design_matrix",
    "iter_design_chunks",
]


def design_matrix(
    joined: Relation, features: Sequence[str], scale=None
) -> np.ndarray:
    """Extract the [m, k] feature matrix from a materialized join, applying
    lazy view rescaling (paper §4.2) when ``scale`` is given.  The one-chunk
    case of ``iter_design_chunks`` — single source of truth for column
    extraction/transform semantics."""
    m = joined.num_rows
    if m == 0:
        return np.zeros((0, len(features)))
    return next(iter_design_chunks(joined, features, m, scale=scale))


@jax.jit
def _gram_jnp(x):
    ones = jnp.ones((x.shape[0],), dtype=x.dtype)
    hi = jax.lax.Precision.HIGHEST  # f32 sums, not one bf16 pass on a TPU
    return jnp.matmul(x.T, x, precision=hi), jnp.matmul(x.T, ones, precision=hi)


def cofactors_from_matrix(
    x: np.ndarray, features: Sequence[str], use_kernel: bool = False
) -> Cofactors:
    """Gram-matrix cofactors of an already-materialized design matrix."""
    m = x.shape[0]
    if use_kernel:
        from repro.kernels import ops as kops

        quad = np.asarray(kops.gram(jnp.asarray(x, dtype=jnp.float32)))
        lin = np.asarray(jnp.asarray(x, dtype=jnp.float32).sum(axis=0))
    else:
        quad, lin = _gram_jnp(jnp.asarray(x, dtype=jnp.float32))
        quad, lin = np.asarray(quad), np.asarray(lin)
    return Cofactors(
        count=float(m),
        lin=lin.astype(np.float64),
        quad=quad.astype(np.float64),
        features=list(features),
    )


def iter_design_chunks(
    joined: Relation,
    features: Sequence[str],
    chunk_rows: int,
    scale=None,
) -> Iterator[np.ndarray]:
    """Yield the design matrix of ``joined`` in [≤chunk_rows, k] slices
    without ever stacking the full [m, k] matrix."""
    if chunk_rows <= 0:
        raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
    m = joined.num_rows
    cols = [joined.column(f) for f in features]
    for lo in range(0, m, chunk_rows):
        hi = min(lo + chunk_rows, m)
        chunk = []
        for f, c in zip(features, cols):
            part = c[lo:hi].astype(np.float64)
            if scale is not None:
                part = scale.transform(f, part)
            chunk.append(part)
        if chunk:
            yield np.stack(chunk, axis=1)
        else:
            yield np.zeros((hi - lo, 0))


def cofactors_streaming(
    chunks: Union[np.ndarray, Iterable[np.ndarray]],
    features: Sequence[str],
    chunk_rows: Optional[int] = None,
    use_kernel: bool = True,
) -> Cofactors:
    """Fold an arbitrarily long stream of design-matrix row chunks into one
    ``Cofactors`` — each chunk's Gram runs through the Pallas ``gram``
    kernel (``use_kernel=False``: plain jnp) and the per-chunk aggregates
    sum via ``Cofactors.__add__``.  Peak device memory is one chunk plus
    the k×k accumulator, independent of the total row count.

    ``chunks`` is either an iterable of [m_i, k] arrays or a single [m, k]
    matrix together with ``chunk_rows`` (split on the host, streamed to the
    device chunk-by-chunk).
    """
    features = list(features)
    if isinstance(chunks, np.ndarray):
        if chunk_rows is None:
            raise ValueError("chunk_rows required when passing one matrix")
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
        m = chunks.shape[0]
        x = chunks
        chunks = (
            x[lo : min(lo + chunk_rows, m)] for lo in range(0, m, chunk_rows)
        )
    k = len(features)
    total = Cofactors(
        count=0.0,
        lin=np.zeros((k,), dtype=np.float64),
        quad=np.zeros((k, k), dtype=np.float64),
        features=features,
    )
    for chunk in chunks:
        if chunk.shape[1] != k:
            raise ValueError(
                f"chunk has {chunk.shape[1]} columns, expected {k} features"
            )
        if chunk.shape[0] == 0:
            continue
        total = total + cofactors_from_matrix(
            chunk, features, use_kernel=use_kernel
        )
    return total


def cofactors_grouped(
    x: np.ndarray,
    seg: np.ndarray,
    num_groups: int,
    features: Sequence[str],
    use_kernel: bool = True,
) -> List[Cofactors]:
    """Per-group cofactors of a partition labeling in one fused pass.

    Appends the intercept column (u = [1, x]) and runs the Pallas
    ``segment_gram`` kernel, whose [G, k+1, k+1] output carries every
    group's count / lin / quad at once.  Summing the returned list with
    ``Cofactors.__add__`` reproduces the global cofactors — the per-shard
    building block of the distributed delta path.  Out-of-range segment
    ids contribute to no group (matching the kernel's zero-one-hot-row
    semantics) on both paths.
    """
    m, k = x.shape
    u = np.concatenate([np.ones((m, 1), dtype=np.float64), x], axis=1)
    if use_kernel:
        from repro.kernels import ops as kops

        blocks = np.asarray(
            kops.segment_gram(
                jnp.asarray(u, dtype=jnp.float32),
                jnp.asarray(seg, dtype=jnp.int32),
                num_groups,
            ),
            dtype=np.float64,
        )
    else:
        seg = np.asarray(seg)
        keep = (seg >= 0) & (seg < num_groups)
        blocks = np.zeros((num_groups, k + 1, k + 1), dtype=np.float64)
        uk = u[keep]
        np.add.at(blocks, seg[keep], uk[:, :, None] * uk[:, None, :])
    return [
        Cofactors(
            count=float(b[0, 0]),
            lin=b[0, 1:].copy(),
            quad=b[1:, 1:].copy(),
            features=list(features),
        )
        for b in blocks
    ]


def cofactors_materialized(
    store: Store,
    features: Sequence[str],
    relations: Optional[Sequence[str]] = None,
    use_kernel: bool = False,
    scale=None,
    chunk_rows: Optional[int] = None,
) -> Cofactors:
    """The non-factorized ("noPre") path: flat join, then X^T X.  With
    ``chunk_rows`` the Gram accumulates through ``cofactors_streaming`` so
    only one chunk of the design matrix is resident at a time."""
    joined = store.materialize_join(relations)
    if chunk_rows is not None:
        return cofactors_streaming(
            iter_design_chunks(joined, features, chunk_rows, scale=scale),
            features,
            use_kernel=use_kernel,
        )
    x = design_matrix(joined, features, scale=scale)
    return cofactors_from_matrix(x, features, use_kernel=use_kernel)


def cofactors_row_engine(
    store: Store,
    features: Sequence[str],
    relations: Optional[Sequence[str]] = None,
    scale=None,
) -> Cofactors:
    """Row-at-a-time interpreted engine (disk-row-engine proxy for Fig. 9).

    Deliberately tuple-oriented: iterates Python-level rows and accumulates
    scalar products, the way a Volcano-style executor touches data.
    """
    joined = store.materialize_join(relations)
    x = design_matrix(joined, features, scale=scale)
    k = len(features)
    quad = [[0.0] * k for _ in range(k)]
    lin = [0.0] * k
    m = 0
    for row in x:  # noqa: B007 — intentionally interpreted
        m += 1
        for i in range(k):
            xi = float(row[i])
            lin[i] += xi
            for j in range(i, k):
                quad[i][j] += xi * float(row[j])
    quad_np = np.asarray(quad)
    quad_np = quad_np + np.triu(quad_np, 1).T  # symmetry (paper: half computed)
    return Cofactors(
        count=float(m),
        lin=np.asarray(lin),
        quad=quad_np,
        features=list(features),
    )
