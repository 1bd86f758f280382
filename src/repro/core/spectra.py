"""Synthetic test matrices with controlled spectra (paper §4, Figs 2-4).

The paper constructs A = U Sigma V^T with random orthogonal U, V and three
spectral profiles:

  (i)   fast decay:   sigma_i = 1 / i^2
  (ii)  sharp decay:  sigma_i = 1e-4 + 1 / (1 + exp(i + 1 - beta))
  (iii) slow decay:   sigma_i = 1 / i^0.1
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.sketch import sketch_matrix

DecayKind = Literal["fast", "sharp", "slow"]


def spectrum(n: int, kind: DecayKind, beta: float = 50.0, dtype=jnp.float32) -> jax.Array:
    i = jnp.arange(1, n + 1, dtype=dtype)
    if kind == "fast":
        return 1.0 / i**2
    if kind == "sharp":
        return 1e-4 + 1.0 / (1.0 + jnp.exp(i + 1.0 - beta))
    if kind == "slow":
        return 1.0 / i**0.1
    raise ValueError(f"unknown decay kind: {kind}")


def random_orthogonal(n: int, cols: int, seed: int, dtype=jnp.float32) -> jax.Array:
    """n x cols matrix with orthonormal columns (QR of a Gaussian)."""
    G = sketch_matrix(n, cols, seed, dtype=dtype)
    Q, R = jnp.linalg.qr(G, mode="reduced")  # repro: noqa[RL006]: test-matrix synthesis, not a solve path
    # Fix signs for determinism across backends.
    return Q * jnp.sign(jnp.diag(R))[None, :]


@functools.partial(jax.jit, static_argnames=("m", "n", "kind", "seed", "dtype"))
def make_test_matrix(
    m: int, n: int, kind: DecayKind, seed: int = 0, beta: float = 50.0, dtype=jnp.float32
) -> tuple[jax.Array, jax.Array]:
    """A = U diag(sigma) V^T (m >= n). Returns (A, sigma)."""
    r = min(m, n)
    sig = spectrum(r, kind, beta, dtype)
    U = random_orthogonal(m, r, seed, dtype)
    V = random_orthogonal(n, r, seed + 1, dtype)
    # HIGHEST: on a TPU the default f32 matmul is one bf16 pass, which would
    # leave A's spectrum off `sig` by ~1e-3 relative
    A = jnp.matmul(U * sig[None, :], V.T, precision=jax.lax.Precision.HIGHEST)
    return A, sig
