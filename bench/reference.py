"""The plain reference that decides `correct`, and the control it is held to.

A solve is judged by what it returned, against facts that do not depend on
the program.  What those facts are belongs to the configuration's kind
(bench/kinds/<kind>.py: for a dense matrix of known spectrum, the optimal
error and the singular values themselves); the arithmetic every kind can
share is here, all of it at `highest` precision:

- `residual_sums`: ||A - U diag(S) Vt||_F^2 and ||A||_F^2, walking A's rows
  in blocks (on each chip's own rows, where A is row-sharded);
- `orth_errors`: max |U^T U - I| and max |Vt Vt^T - I|;
- `identical`: whether two solves of one matrix under different sketch
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


def _walk_rows(A, U, svt):
    """[||A - U S Vt||_F^2, ||A||_F^2] of the rows at hand, in blocks so
    that no rows x n temporary exists."""
    rows, n = A.shape
    b = RESIDUAL_BLOCK_ROWS if rows % RESIDUAL_BLOCK_ROWS == 0 else rows

    def body(i, acc):
        Ab = jax.lax.dynamic_slice_in_dim(A, i * b, b)
        Ub = jax.lax.dynamic_slice_in_dim(U, i * b, b)
        R = Ab - matmul(Ub, svt, "highest")
        return acc + jnp.stack([jnp.sum(R * R), jnp.sum(Ab * Ab)])

    return jax.lax.fori_loop(0, rows // b, body, jnp.zeros(2, jnp.float32))


_residual_sums = jax.jit(_walk_rows)


@functools.lru_cache(maxsize=None)
def _residual_sums_rows(mesh, axis: str):
    """`_walk_rows` on each chip's own rows of a row-sharded A and U, the
    two sums added over the chips."""
    rows = jax.sharding.PartitionSpec(axis, None)

    def local(A, U, svt):
        return jax.lax.psum(_walk_rows(A, U, svt), axis)

    # check_vma off: the block walk's carry starts replicated and becomes
    # per-chip, which the psum makes replicated again
    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(rows, rows, jax.sharding.PartitionSpec()),
                                 out_specs=jax.sharding.PartitionSpec(), check_vma=False))


def residual_sums(A, factors, sharding=None) -> np.ndarray:
    """[||A - U diag(S) Vt||_F^2, ||A||_F^2] in float64, at `highest`;
    `sharding` is (mesh, axis) where A is row-sharded, and U is then laid
    over the same rows."""
    U, S, Vt = factors
    S = jnp.asarray(S, jnp.float32)
    svt = S[:, None] * jnp.asarray(Vt, jnp.float32)
    U = jnp.asarray(U, jnp.float32)
    if sharding is None:
        sums = _residual_sums(A, U, svt)
    else:
        mesh, axis = sharding
        U = jax.device_put(U, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(axis, None)))
        svt = jax.device_put(svt, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
        sums = _residual_sums_rows(mesh, axis)(A, U, svt)
    return np.asarray(jax.device_get(sums), np.float64)


def _orth_err(X):
    """max |X^T X - I| of X with orthonormal columns."""
    G = matmul(X.T, X, "highest")
    return jnp.max(jnp.abs(G - jnp.eye(G.shape[0], dtype=G.dtype)))


@jax.jit
def _orth_errors(U, Vt):
    return jnp.stack([_orth_err(U), _orth_err(Vt.T)])


def orth_errors(U, Vt) -> np.ndarray:
    """[max |U^T U - I|, max |Vt Vt^T - I|] in float64."""
    return np.asarray(jax.device_get(_orth_errors(U, Vt)), np.float64)


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


def _meeting(a, factors, eps: float) -> tuple:
    """The fewest leading components of a full-rank `factors` whose
    posterior residual meets the relative tolerance eps."""
    U, S, Vt = factors
    norm_sq = float(jnp.sum(jnp.square(a)))
    tail = norm_sq - np.cumsum(np.asarray(jax.device_get(S), np.float64) ** 2)
    met = np.nonzero(tail <= eps ** 2 * norm_sq)[0]
    r = int(met[0]) + 1 if met.size else len(tail)
    return U[:, :r], S[:r], Vt[:r]


def decompose(precision: str, oversample: int, power_iters: int):
    """A stand-in for `repro.linalg.decompose(a, spec, seed=...)` that runs
    `rsvd` at `precision`: what the control plants in a run.  Under a
    `Tolerance` it factors at full rank and keeps the fewest leading
    components whose residual, ||A||_F^2 less their energy, meets eps."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Decomposition:
        factors: tuple

    def call(a, spec, seed=0, **_):
        a = jnp.asarray(getattr(a, "array", a))   # a ShardedOp's array
        eps = getattr(spec, "eps", None)
        k = min(a.shape) if eps is not None else spec.k
        factors = rsvd(a, seed_words(seed), k=k, oversample=oversample,
                       power_iters=power_iters, precision=precision)
        if eps is not None:
            factors = _meeting(a, factors, eps)
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
