"""The plain reference that decides `correct`, and the control it is held to.

A solve is judged by what it returned, against facts that do not depend on
the program: every pool matrix has the exact spectrum sigma_i = 1/i^2 by
construction, so the optimal rank-k error is known, and the factors'
residual is computed here, row block by row block, at `highest` precision.
The numbers (all "lower is better"):

- residual_ratio: ||A - U diag(S) Vt||_F / ||A||_F over the optimal
  sqrt(sum_{i>k} sigma_i^2 / sum_i sigma_i^2);
- sigma_rel_err_top1, sigma_rel_err_top10: the largest relative error of
  the leading 1 and 10 singular values against the exact spectrum;
- u_orth_err, v_orth_err: max |U^T U - I| and max |Vt Vt^T - I|;
- repeat_identical: whether two solves of one matrix under different sketch
  seeds returned bit-identical factors, which only a kept result does.

`rsvd` is a straightforward randomized SVD (Gaussian sketch, q power
iterations re-orthonormalized by Householder QR, LAPACK-style SVD of B) in
plain jax.numpy.  Put in the program's place with its products at one bf16
pass ("bf16"), the precision below the float32 that the configurations
state, it is the control; "high" (three bf16 passes on a TPU) is kept for
the record.  `decompose` and `service` put it in the program's place,
behind the program's own call signatures.

This module imports nothing of the program and takes nothing it made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from sources import seed_key, seed_words

PRECISIONS = ("highest", "high", "bf16")
NUMBERS = ("residual_ratio", "sigma_rel_err_top1", "sigma_rel_err_top10",
           "u_orth_err", "v_orth_err")
RESIDUAL_BLOCK_ROWS = 8192


def matmul(a, b, precision: str):
    if precision in ("highest", "high"):
        # on a TPU, HIGH is three bf16 passes; the CPU computes f32 either way
        p = jax.lax.Precision.HIGHEST if precision == "highest" else jax.lax.Precision.HIGH
        return jnp.matmul(a, b, precision=p)
    if precision == "bf16":
        # operands rounded to bf16, every product exact in f32 and summed in
        # f32: one bf16 pass, identically on every backend
        def bf16_values(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        return jnp.matmul(bf16_values(a), bf16_values(b),
                          precision=jax.lax.Precision.DEFAULT)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def optimal_ratio(sigma: np.ndarray, k: int) -> float:
    """||A - A_k||_F / ||A||_F from the exact spectrum."""
    sq = np.asarray(sigma, np.float64) ** 2
    return float(np.sqrt(sq[k:].sum() / sq.sum()))


@jax.jit
def _residual_sums(A, U, svt):
    """[||A - U S Vt||_F^2, ||A||_F^2], walking A's rows in blocks so that
    no m x n temporary exists."""
    rows, n = A.shape
    b = RESIDUAL_BLOCK_ROWS if rows % RESIDUAL_BLOCK_ROWS == 0 else rows

    def body(i, acc):
        Ab = jax.lax.dynamic_slice_in_dim(A, i * b, b)
        Ub = jax.lax.dynamic_slice_in_dim(U, i * b, b)
        R = Ab - matmul(Ub, svt, "highest")
        return acc + jnp.stack([jnp.sum(R * R), jnp.sum(Ab * Ab)])

    return jax.lax.fori_loop(0, rows // b, body, jnp.zeros(2, jnp.float32))


def _orth_err(X):
    """max |X^T X - I| of X with orthonormal columns."""
    G = matmul(X.T, X, "highest")
    return jnp.max(jnp.abs(G - jnp.eye(G.shape[0], dtype=G.dtype)))


@jax.jit
def _orth_errors(U, Vt):
    return jnp.stack([_orth_err(U), _orth_err(Vt.T)])


def judge(A, factors, sigma: np.ndarray, k: int) -> dict:
    """The numbers of one solve.  A shape or a value that is not finite
    reads as inf."""
    U, S, Vt = factors
    m, n = A.shape
    if U.shape != (m, k) or S.shape != (k,) or Vt.shape != (k, n):
        return {name: float("inf") for name in NUMBERS}
    S = jnp.asarray(S, jnp.float32)
    svt = S[:, None] * jnp.asarray(Vt, jnp.float32)
    sums = np.asarray(jax.device_get(
        _residual_sums(A, jnp.asarray(U, jnp.float32), svt)), np.float64)
    orth = np.asarray(jax.device_get(_orth_errors(U, Vt)), np.float64)
    s = np.asarray(jax.device_get(S), np.float64)
    rel = np.abs(s - sigma[:k]) / sigma[:k]
    out = {
        "residual_ratio": float(np.sqrt(sums[0] / sums[1]) / optimal_ratio(sigma, k)),
        "sigma_rel_err_top1": float(rel[0]),
        "sigma_rel_err_top10": float(rel[:10].max()),
        "u_orth_err": float(orth[0]),
        "v_orth_err": float(orth[1]),
    }
    return {name: (v if np.isfinite(v) else float("inf")) for name, v in out.items()}


def identical(factors_a, factors_b) -> float:
    """1.0 where two solves returned bit-identical factors, else 0.0."""
    same = all(np.array_equal(np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)))
               for x, y in zip(factors_a, factors_b))
    return float(same)


@functools.partial(jax.jit,
                   static_argnames=("k", "oversample", "power_iters", "precision"))
def rsvd(A, seed_words, *, k: int, oversample: int, power_iters: int,
         precision: str):
    """Rank-k randomized SVD of A, every product at `precision`."""
    omega = jax.random.normal(jax.random.fold_in(seed_key(seed_words), 7),
                              (A.shape[1], k + oversample), jnp.float32)
    Y = matmul(A, omega, precision)
    for _ in range(power_iters):
        Q, _ = jnp.linalg.qr(Y)
        Z = matmul(A.T, Q, precision)
        Qz, _ = jnp.linalg.qr(Z)
        Y = matmul(A, Qz, precision)
    Q, _ = jnp.linalg.qr(Y)
    B = matmul(Q.T, A, precision)
    Ub, S, Vt = jnp.linalg.svd(B, full_matrices=False)
    return matmul(Q, Ub[:, :k], precision), S[:k], Vt[:k]


def decompose(precision: str, oversample: int, power_iters: int):
    """A stand-in for `repro.linalg.decompose(a, Rank(k), seed=...)` that
    runs `rsvd` at `precision`: what the control plants in a run."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Decomposition:
        factors: tuple

    def call(a, spec, seed=0, **_):
        factors = rsvd(jnp.asarray(a), seed_words(seed), k=spec.k, oversample=oversample,
                       power_iters=power_iters, precision=precision)
        return Decomposition(factors)

    return call


def service(precision: str, oversample: int, power_iters: int):
    """A stand-in for `repro.serve.decomp.DecompositionService` whose
    `submit` runs `rsvd` at `precision` and returns a future resolved with
    its ready factors: what the control plants in an open-loop run."""
    from concurrent.futures import Future

    call = decompose(precision, oversample, power_iters)

    class Metrics:
        @staticmethod
        def records() -> list:
            return []

    class Service:
        def __init__(self, *, max_batch: int = 8):
            self.metrics = Metrics()

        def submit(self, a, spec, seed=0, **_):
            dec = call(a, spec, seed=seed)
            jax.block_until_ready(dec.factors)
            fut = Future()
            fut.set_result(dec)
            return fut

        def flush(self) -> None:
            pass

        def close(self, timeout=None) -> None:
            pass

    return Service
