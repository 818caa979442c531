"""Binary SVM training in JAX (paper Sec. II-A).

Solver
------
Dual coordinate ascent on the box-constrained dual.  The bias is folded into
the kernel ("bias-as-feature": K' = K + 1), which removes the equality
constraint ``sum(alpha * y) == 0`` and makes every coordinate update an
independent 1-D clip — ideal for ``lax.fori_loop`` and for ``vmap`` over
(C, gamma) hyper-parameter grids and CV folds.

    max_a  sum(a) - 1/2 aT Q a,   Q_ij = y_i y_j K'(x_i, x_j),  0 <= a_i <= C_i

Per-sample box ``C_i`` doubles as a *mask*: setting ``C_i = 0`` freezes a
sample at alpha 0, which is how CV folds and padded batches are trained
without data-dependent shapes.

The recovered model is  f(x) = sum_j a_j y_j (K(x_j, x) + 1)  so the bias is
``b = sum_j a_j y_j``; for the linear kernel the primal weight vector is
``w = sum_j a_j y_j x_j`` (paper Eq. 3).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernels as kern
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class SVMModel:
    """A trained binary SVM. Arrays are host numpy for easy serialization."""

    kind: str  # 'linear' | 'rbf' | 'sech2' | 'hw'
    support_x: np.ndarray  # (m, d)
    support_y: np.ndarray  # (m,) in {-1, +1}
    alpha: np.ndarray  # (m,) > 0
    bias: float
    gamma: float  # only meaningful for RBF-family kernels
    c: float
    # Linear primal view (paper Eq. 3); None for rbf.
    w: Optional[np.ndarray] = None
    # Callable kernel for kind == 'hw' (hardware-in-the-loop training);
    # excluded from equality/serialization concerns by compare=False.
    kernel_fn: Optional[object] = dataclasses.field(default=None, compare=False)

    @property
    def n_support(self) -> int:
        return int(self.support_x.shape[0])


# --------------------------------------------------------------------------
# Core solver
# --------------------------------------------------------------------------


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """The ``api/compiled.py`` convention: None -> Pallas only on TPU
    (the CPU container would run the interpreter; pass ``interpret=True``
    alongside ``use_pallas=True`` to exercise that path deliberately)."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def cv_lanes_accuracy_pallas(
    x: jnp.ndarray,           # (P, n, d)
    y: jnp.ndarray,           # (P, n)
    fold_masks: jnp.ndarray,  # (P, F, n) 1 train / 0 held-out
    valid: jnp.ndarray,       # (P, n) 1 real / 0 padding
    gammas_pg: jnp.ndarray,   # (P, G)
    cs: jnp.ndarray,          # (C,)
    kind: str,
    n_epochs: int,
    interpret: Optional[bool] = None,
    block: int = 16,
) -> jnp.ndarray:
    """(P, G, C) mean CV accuracy through the fused Pallas solver.

    The Gram-free twin of the ``_cell_cv_accuracy`` reduction: lanes are
    the C-major flattening of (C, fold) — matching ``jnp.repeat(cs,
    n_f)`` in the blocked path — the box folds train-mask and validity
    in, and validation consumes the solver's fused margin output ``f``
    directly (``kp @ (alpha * y)`` never materializes a Gram).
    """
    p, n_f, n = fold_masks.shape
    n_c = cs.shape[0]
    m_lanes = jnp.tile(fold_masks, (1, n_c, 1))          # (P, C*F, n)
    c_lanes = jnp.repeat(cs, n_f)                        # (C*F,)
    c_box = c_lanes[None, :, None] * m_lanes * valid[:, None, :]
    _, f = kops.solve_lanes(x, y, c_box, gammas_pg, kind=kind,
                            n_epochs=n_epochs, block=block,
                            interpret=interpret)
    pred = jnp.where(f >= 0.0, 1.0, -1.0)                # (P, G, C*F, n)
    val = (1.0 - m_lanes) * valid[:, None, :]            # (P, C*F, n)
    hit = ((pred == y[:, None, None, :]) * val[:, None]).sum(-1)
    acc = hit / jnp.clip(val.sum(-1), 1.0, None)[:, None]
    return acc.reshape(p, gammas_pg.shape[1], n_c, n_f).mean(-1)


@partial(jax.jit, static_argnames=("n_epochs",))
def dual_coordinate_ascent(
    kp: jnp.ndarray,  # (n, n) kernel matrix WITH bias term folded in (K + 1)
    y: jnp.ndarray,  # (n,) in {-1, +1}
    c_box: jnp.ndarray,  # (n,) per-sample box (0 masks the sample out)
    n_epochs: int = 200,
) -> jnp.ndarray:
    """Gauss-Seidel dual coordinate ascent; returns alpha (n,)."""
    n = kp.shape[0]
    qdiag = jnp.clip(jnp.diag(kp), 1e-12, None)

    def body(t, carry):
        alpha, f = carry  # f_i = sum_j alpha_j y_j K'_ij  (margin pre-y)
        i = t % n
        g = 1.0 - y[i] * f[i]
        a_new = jnp.clip(alpha[i] + g / qdiag[i], 0.0, c_box[i])
        delta = a_new - alpha[i]
        f = f + delta * y[i] * kp[:, i]
        alpha = alpha.at[i].set(a_new)
        return alpha, f

    alpha0 = jnp.zeros((n,), kp.dtype)
    f0 = jnp.zeros((n,), kp.dtype)
    alpha, _ = jax.lax.fori_loop(0, n_epochs * n, body, (alpha0, f0))
    return alpha


def _gram(kind: str, x: jnp.ndarray, gamma) -> jnp.ndarray:
    return kern.kernel_matrix(kind, x, x, gamma) + 1.0  # bias-as-feature


def train_binary(
    x: np.ndarray,
    y: np.ndarray,
    kind="linear",
    gamma: float = 1.0,
    c: float = 1.0,
    n_epochs: int = 200,
    sv_tol: float = 1e-6,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> SVMModel:
    """Train one binary SVM and extract its support set (host-side).

    ``kind`` may be a callable kernel (hardware-in-the-loop), recorded as
    kind='hw' with the callable kept on the model.  ``use_pallas`` routes
    the solve through the fused Gram-free Pallas kernel for the string
    kinds (alphas agree with the reference to f32 round-off); callables
    always take the materialized-Gram path.
    """
    xj = jnp.asarray(x, jnp.float32)
    yj = jnp.asarray(y, jnp.float32)
    if resolve_use_pallas(use_pallas) and isinstance(kind, str):
        a_lanes, _ = kops.solve_lanes(
            xj[None], yj[None],
            jnp.full((1, 1, x.shape[0]), float(c), jnp.float32),
            jnp.full((1, 1), float(gamma), jnp.float32),
            kind=kind, n_epochs=n_epochs, interpret=interpret)
        alpha = np.asarray(a_lanes[0, 0, 0])
    else:
        kp = _gram(kind, xj, gamma)
        alpha = np.asarray(dual_coordinate_ascent(
            kp, yj, jnp.full((x.shape[0],), float(c)), n_epochs))
    sv = alpha > sv_tol
    bias = float(np.sum(alpha[sv] * y[sv]))
    w = None
    if kind == "linear":
        w = np.asarray((alpha[sv] * y[sv]) @ x[sv], np.float64)
    return SVMModel(
        kind=kind if isinstance(kind, str) else "hw",
        support_x=np.asarray(x[sv], np.float64),
        support_y=np.asarray(y[sv], np.float64),
        alpha=np.asarray(alpha[sv], np.float64),
        bias=bias,
        gamma=float(gamma),
        c=float(c),
        w=w,
        kernel_fn=None if isinstance(kind, str) else kind,
    )


def decision_function(model: SVMModel, x: np.ndarray) -> np.ndarray:
    """f(x) without the sign (paper Eq. 1)."""
    if model.kind == "linear" and model.w is not None:
        return np.asarray(x, np.float64) @ model.w + model.bias
    kind = model.kernel_fn if model.kernel_fn is not None else model.kind
    k = np.asarray(
        kern.kernel_matrix(
            kind, jnp.asarray(x, jnp.float32),
            jnp.asarray(model.support_x, jnp.float32), model.gamma,
        ),
        np.float64,
    )
    return k @ (model.alpha * model.support_y) + model.bias


def predict(model: SVMModel, x: np.ndarray) -> np.ndarray:
    """Hard labels in {-1, +1}; zeros break toward +1 (comparator convention)."""
    return np.where(decision_function(model, x) >= 0.0, 1.0, -1.0)


def accuracy(model: SVMModel, x: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(predict(model, x) == y))


# --------------------------------------------------------------------------
# Batched training: hyper-parameter grids and CV folds via vmap
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kind", "n_epochs"))
def _train_eval_masked(
    x: jnp.ndarray,
    y: jnp.ndarray,
    train_mask: jnp.ndarray,  # (n,) 1.0 train / 0.0 held-out
    gamma: jnp.ndarray,
    c: jnp.ndarray,
    kind: str,
    n_epochs: int,
):
    """Train on masked subset, return (alpha, val_acc on the complement)."""
    kp = kern.kernel_matrix(kind, x, x, gamma) + 1.0
    alpha = dual_coordinate_ascent(kp, y, c * train_mask, n_epochs)
    f = kern.matmul(kp, alpha * y)
    pred = jnp.where(f >= 0.0, 1.0, -1.0)
    val = 1.0 - train_mask
    val_acc = jnp.sum((pred == y) * val) / jnp.clip(jnp.sum(val), 1.0, None)
    return alpha, val_acc


def cv_grid_accuracy(
    x: np.ndarray,
    y: np.ndarray,
    kind: str,
    gammas: np.ndarray,
    cs: np.ndarray,
    n_folds: int = 5,
    n_epochs: int = 120,
    seed: int = 0,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """(len(gammas), len(cs)) mean CV accuracy — all folds x grid in one vmap.

    With ``use_pallas`` (string kinds) the whole grid runs through the
    fused solver lanes instead: no Gram is ever materialized, and the
    blocked update sequence replaces the reference solver's (accuracies
    agree to f32 round-off, DESIGN.md §7).
    """
    n = x.shape[0]
    rng = np.random.RandomState(seed)
    fold_of = rng.permutation(n) % n_folds
    masks = np.stack([(fold_of != f).astype(np.float32) for f in range(n_folds)])

    if resolve_use_pallas(use_pallas) and isinstance(kind, str):
        acc = cv_lanes_accuracy_pallas(
            jnp.asarray(x, jnp.float32)[None],
            jnp.asarray(y, jnp.float32)[None],
            jnp.asarray(masks)[None], jnp.ones((1, n), jnp.float32),
            jnp.asarray(gammas, jnp.float32)[None],
            jnp.asarray(cs, jnp.float32),
            kind=kind, n_epochs=n_epochs, interpret=interpret)
        return np.asarray(acc[0])

    gg, cc = np.meshgrid(np.asarray(gammas, np.float32),
                         np.asarray(cs, np.float32), indexing="ij")
    gflat, cflat = gg.ravel(), cc.ravel()

    fn = jax.vmap(  # over grid
        jax.vmap(  # over folds
            lambda m, g, c: _train_eval_masked(
                jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
                m, g, c, kind, n_epochs,
            )[1],
            in_axes=(0, None, None),
        ),
        in_axes=(None, 0, 0),
    )
    accs = fn(jnp.asarray(masks), jnp.asarray(gflat), jnp.asarray(cflat))
    return np.asarray(accs.mean(axis=1)).reshape(len(gammas), len(cs))


def fit_best(
    x: np.ndarray,
    y: np.ndarray,
    kind,
    gammas: np.ndarray | None = None,
    cs: np.ndarray | None = None,
    n_folds: int = 5,
    n_epochs: int = 200,
    seed: int = 0,
    cv_epochs: int | None = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> tuple[SVMModel, float]:
    """Grid-search (gamma, C) by CV, refit on the full set. Returns (model, cv_acc).

    ``cv_epochs`` sets the solver epochs used while training CV folds;
    the default keeps the historical policy ``max(60, n_epochs // 2)``
    (fold models only need to rank hyper-parameters, not converge fully).
    The final full-set refit always runs the full ``n_epochs``.
    ``use_pallas``/``interpret`` route both the CV grid and the refit
    through the fused Gram-free solver (string kinds only).
    """
    if cs is None:
        cs = np.logspace(-1, 3, 7)
    if kind == "linear":
        gammas = np.array([1.0])
    elif gammas is None:
        gammas = np.logspace(-1, 2, 7)
    if cv_epochs is None:
        cv_epochs = max(60, n_epochs // 2)
    acc = cv_grid_accuracy(x, y, kind, gammas, cs, n_folds, int(cv_epochs),
                           seed, use_pallas=use_pallas, interpret=interpret)
    gi, ci = np.unravel_index(np.argmax(acc), acc.shape)
    model = train_binary(x, y, kind, float(gammas[gi]), float(cs[ci]),
                         n_epochs, use_pallas=use_pallas,
                         interpret=interpret)
    return model, float(acc[gi, ci])
