"""The matrices a cell factors, built on the device from the run's seed.

Every pool member is A = U diag(sigma) V^T with sigma_i = 1/i^2, U and V
the Q factors of Gaussian matrices.  Member 0 is the base; member j > 0 is
the base with its rows permuted and sign-flipped, which keeps the singular
values exactly.  The whole pool comes out of one jitted call.

Everything here is the benchmark's own: it imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def spectrum(n: int) -> np.ndarray:
    """sigma_i = 1/i^2, i = 1..n, in float64."""
    return 1.0 / np.arange(1, n + 1, dtype=np.float64) ** 2


def seed_key(seed):
    """A PRNG key from a uint32[2] array holding the run seed's low and high
    words, so every seed runs the same compiled program."""
    return jax.random.fold_in(jax.random.key(seed[0]), seed[1])


def seed_words(seed: int) -> np.ndarray:
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2^64)")
    return np.array([seed % 2 ** 32, seed // 2 ** 32], np.uint32)


def _orthonormal(key, rows: int, cols: int):
    Q, R = jnp.linalg.qr(jax.random.normal(key, (rows, cols), jnp.float32))
    return Q * jnp.sign(jnp.diagonal(R))[None, :]


@functools.partial(jax.jit, static_argnames=("m", "n", "pool"))
def build(seed, *, m: int, n: int, pool: int):
    """The pool's `pool` matrices, m x n f32, from the seed's words."""
    key = seed_key(seed)
    r = min(m, n)
    U = _orthonormal(jax.random.fold_in(key, 0), m, r)
    V = _orthonormal(jax.random.fold_in(key, 1), n, r)
    sig = 1.0 / jnp.arange(1, r + 1, dtype=jnp.float32) ** 2
    base = jnp.matmul(U * sig[None, :], V.T, precision=jax.lax.Precision.HIGHEST)
    members = [base]
    for j in range(1, pool):
        k_perm, k_sign = jax.random.split(jax.random.fold_in(key, 2 + j))
        perm = jax.random.permutation(k_perm, m)
        signs = jax.random.rademacher(k_sign, (m,), jnp.float32)
        members.append(base[perm] * signs[:, None])
    return tuple(members)


class Pool:
    """The cell's matrices: `members[j]` is what call j passes to the
    program (a device array or a numpy array), `device_member(j)` the same
    matrix on the device for the reference, and `sigma` the exact spectrum
    every member shares."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, device):
        if cfg["dtype"] != "float32" or cfg["spectrum"] != "inverse_square":
            raise ValueError(f"unsupported configuration {cfg['name']}")
        self.device = device
        self.sigma = spectrum(min(cfg["m"], cfg["n"]))
        with jax.default_device(device):
            members = build(seed_words(seed), m=cfg["m"], n=cfg["n"],
                            pool=traffic["pool"])
        if traffic["residency"] == "host":
            members = [np.asarray(A) for A in jax.device_get(members)]
        self.members = list(jax.block_until_ready(members))

    def device_member(self, j: int):
        A = self.members[j]
        if isinstance(A, np.ndarray):
            return jax.device_put(A, self.device)
        return A
