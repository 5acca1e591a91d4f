"""Batch gradient descent on a precomputed cofactor matrix (paper §3.4, §4.4).

The data-dependent part of the least-squares gradient factors as

    S_j = Σ_k θ_k · Cofactor[k, j]

so once the cofactor matrix is known every BGD step is a single [p, p] @ [p]
matvec — **independent of the number of training rows m**.  This module
reproduces the paper's convergence procedure faithfully:

* θ has one entry per feature plus the intercept plus the label; the label's
  coefficient is *fixed to −1* (paper §3.2: "y is also considered a feature
  with its corresponding θ fixed to −1").
* update:  ε_j = α · (S_j + 0.006·θ_j)   (ridge term, paper §4.4)
* α starts at 0.003 and is divided by 3 whenever Σ_j |ε_j| grew relative to
  the previous iteration (paper version 1); stop when Σ_j |ε_j| < ε_threshold
  (1e-6; version 3 uses 1e-8), when α < 1e-15, or at the iteration cap.
* version 4's "alternative adjustment" (the paper gives no formula; our
  interpretation, documented here): on an increase the step is *reverted*
  before shrinking α, and α grows by 5% on successful steps — a classic
  bold-driver schedule.  It reproduces the paper's observation that v4 is
  slightly more accurate at equal cost.

The loop runs on-device via ``jax.lax.while_loop``.  A ``bgd_data`` variant
implements the non-factorized ("noPre") baseline: mathematically the same
update, but S is recomputed from the materialized data every iteration
(two [m, p] matmuls per step), so its cost scales with m.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["GDConfig", "GDResult", "bgd_cofactor", "bgd_data", "solve_cofactor"]


@dataclasses.dataclass(frozen=True)
class GDConfig:
    alpha0: float = 0.003
    eps: float = 1e-6  # version 3 sets 1e-8
    ridge: float = 0.006  # the paper's fixed 0.006·θ_j ridge term
    max_iter: int = 200_000  # paper caps at 1e8; configurable
    alpha_min: float = 1e-15
    alpha_strategy: str = "paper"  # "paper" (v1) | "revert" (v4)
    alpha_grow: float = 1.05  # only used by the "revert" strategy
    dtype: jnp.dtype = jnp.float32


@dataclasses.dataclass
class GDResult:
    theta: np.ndarray  # full vector [p]: [intercept, features..., label=-1]
    iterations: int
    alpha: float
    last_update: float

    def trainable(self) -> np.ndarray:
        return self.theta[:-1]


def _mv(a: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Matrix-vector product at full f32 precision (a TPU otherwise takes
    one bf16 pass, which caps the gradient at about three digits)."""
    return jnp.matmul(a, v, precision=jax.lax.Precision.HIGHEST)


def _run_loop(step_fn, theta0, cfg: GDConfig):
    """Shared while_loop driver.  Carry: (θ, α, prev_sum, it, converged)."""

    def cond(carry):
        _, alpha, _, it, converged = carry
        return (~converged) & (it < cfg.max_iter) & (alpha > cfg.alpha_min)

    def body(carry):
        theta, alpha, prev_sum, it, _ = carry
        eps_vec = step_fn(theta, alpha)
        cur_sum = jnp.sum(jnp.abs(eps_vec))
        increase = cur_sum > prev_sum
        if cfg.alpha_strategy == "paper":
            theta_new = theta - eps_vec
            alpha_new = jnp.where(increase, alpha / 3.0, alpha)
            prev_new = cur_sum
        elif cfg.alpha_strategy == "revert":
            theta_new = jnp.where(increase, theta, theta - eps_vec)
            alpha_new = jnp.where(increase, alpha / 3.0, alpha * cfg.alpha_grow)
            prev_new = jnp.where(increase, prev_sum, cur_sum)
        else:
            raise ValueError(f"unknown alpha_strategy {cfg.alpha_strategy}")
        converged = cur_sum < cfg.eps
        return theta_new, alpha_new, prev_new, it + 1, converged

    alpha0 = jnp.asarray(cfg.alpha0, dtype=cfg.dtype)
    prev0 = jnp.asarray(jnp.inf, dtype=cfg.dtype)
    carry = (theta0, alpha0, prev0, jnp.asarray(0, jnp.int32), jnp.asarray(False))
    theta, alpha, last, it, _ = jax.lax.while_loop(cond, body, carry)
    return theta, alpha, last, it


@partial(jax.jit, static_argnames=("cfg",))
def _bgd_cofactor_jit(cof: jnp.ndarray, trainable: jnp.ndarray, cfg: GDConfig):
    p = cof.shape[0]
    theta0 = jnp.zeros((p,), dtype=cfg.dtype).at[-1].set(-1.0)

    def step(theta, alpha):
        s = _mv(cof, theta)  # the whole data scan, collapsed to one matvec
        return alpha * (s + cfg.ridge * theta) * trainable

    return _run_loop(step, theta0, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _bgd_cofactor_penalty_jit(
    cof: jnp.ndarray, pen: jnp.ndarray, trainable: jnp.ndarray, cfg: GDConfig
):
    p = cof.shape[0]
    theta0 = jnp.zeros((p,), dtype=cfg.dtype).at[-1].set(-1.0)

    def step(theta, alpha):
        s = _mv(cof, theta)
        return alpha * (s + _mv(pen, theta)) * trainable

    return _run_loop(step, theta0, cfg)


def bgd_cofactor(
    cof_matrix: np.ndarray,
    cfg: Optional[GDConfig] = None,
    penalty: Optional[np.ndarray] = None,
) -> GDResult:
    """BGD on a cofactor matrix ordered [intercept, features..., label].

    ``penalty``, when given, is a full [p, p] penalty matrix replacing the
    scalar ``cfg.ridge * θ`` term with ``penalty @ θ`` — the generalized
    ridge of the FD-reduced parameter space (``repro.core.fd``).  Its label
    row/column must be zero (θ_label is pinned to −1)."""
    cfg = cfg or GDConfig()
    cof = jnp.asarray(cof_matrix, dtype=cfg.dtype)
    p = cof.shape[0]
    trainable = jnp.ones((p,), dtype=cfg.dtype).at[-1].set(0.0)
    if penalty is None:
        theta, alpha, last, it = _bgd_cofactor_jit(cof, trainable, cfg)
    else:
        theta, alpha, last, it = _bgd_cofactor_penalty_jit(
            cof, jnp.asarray(penalty, dtype=cfg.dtype), trainable, cfg
        )
    return GDResult(
        theta=np.asarray(theta, dtype=np.float64),
        iterations=int(it),
        alpha=float(alpha),
        last_update=float(last),
    )


@partial(jax.jit, static_argnames=("cfg",))
def _bgd_data_jit(z: jnp.ndarray, trainable: jnp.ndarray, cfg: GDConfig):
    p = z.shape[1]
    theta0 = jnp.zeros((p,), dtype=cfg.dtype).at[-1].set(-1.0)

    def step(theta, alpha):
        s = _mv(z.T, _mv(z, theta))  # full data scan, every iteration (noPre)
        return alpha * (s + cfg.ridge * theta) * trainable

    return _run_loop(step, theta0, cfg)


def bgd_data(z: np.ndarray, cfg: Optional[GDConfig] = None) -> GDResult:
    """Non-factorized BGD over the materialized design matrix
    z = [1, x_1..x_n, y] per row — the paper's ``noPre`` baseline."""
    cfg = cfg or GDConfig()
    zj = jnp.asarray(z, dtype=cfg.dtype)
    p = zj.shape[1]
    trainable = jnp.ones((p,), dtype=cfg.dtype).at[-1].set(0.0)
    theta, alpha, last, it = _bgd_data_jit(zj, trainable, cfg)
    return GDResult(
        theta=np.asarray(theta, dtype=np.float64),
        iterations=int(it),
        alpha=float(alpha),
        last_update=float(last),
    )


def solve_cofactor(
    cof_matrix: np.ndarray,
    ridge: float = 0.0,
    penalty: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Beyond-paper: closed-form ridge solve of the normal equations.

    With ordering [intercept, features..., label] and θ_label = −1, the
    stationarity condition  C_tt·θ_t + ridge·θ_t = C_t,label  is a (p−1)
    linear system — solved directly in float64.  Returns the full θ vector.

    ``penalty`` replaces ``ridge·I`` with an arbitrary [p−1, p−1] penalty
    matrix over the trainable coordinates — the generalized ridge the
    FD-reduced solve needs (``repro.core.fd.penalty_blocks``).
    """
    cof = np.asarray(cof_matrix, dtype=np.float64)
    p = cof.shape[0]
    pen = penalty if penalty is not None else ridge * np.eye(p - 1)
    ctt = cof[: p - 1, : p - 1] + pen
    rhs = cof[: p - 1, p - 1]
    theta_t = np.linalg.solve(ctt, rhs)
    return np.concatenate([theta_t, [-1.0]])
