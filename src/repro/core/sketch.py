"""Sketching operators for randomized linear algebra.

The paper's second pillar (besides BLAS-3 reformulation) is a *fast parallel
random number generator* (cuRAND on GPU, reported up to 3x speedup of the
sketch step).  On TPU we go one step further: a *counter-based* stateless RNG
(murmur3-finalizer hash over the element index) that can be evaluated

  * in pure jnp (this module — the oracle / host path), and
  * inside a Pallas kernel tile loop (kernels/sketch_matmul.py), bit-exactly,

so the Gaussian sketch matrix never has to be materialized in HBM, and the
distributed implementation can regenerate identical sketch columns on every
device without any broadcast collective.
"""
from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Counter-based RNG primitive (murmur3 finalizer, 2 rounds with distinct keys)
# ---------------------------------------------------------------------------

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def _murmur_fmix(x: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer; x is uint32."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def hash_u32(idx: jax.Array, seed: jax.Array | int) -> jax.Array:
    """Stateless counter hash: (index, seed) -> uint32.

    Two mixing rounds; the seed enters both rounds so that low-entropy seeds
    still decorrelate streams.
    """
    idx = idx.astype(jnp.uint32)
    seed = jnp.asarray(seed, jnp.uint32)
    h = _murmur_fmix(idx * _GOLDEN + seed)
    h = _murmur_fmix(h ^ (seed * _M1 + np.uint32(0x27220A95)))
    return h


def _u32_to_unit(bits: jax.Array) -> jax.Array:
    """uint32 -> float32 uniform in (0, 1]  (never 0, so log() is safe)."""
    # Take the top 24 bits -> [0, 2^24), then (x + 1) / 2^24 in (0, 1].
    # Cast via int32 (exact below 2^24), as the Pallas kernel must.
    return (bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * np.float32(
        1.0 / 16777216.0
    ) + np.float32(1.0 / 16777216.0)


def uniform_from_index(idx: jax.Array, seed) -> jax.Array:
    return _u32_to_unit(hash_u32(idx, seed))


def normal_from_index(idx: jax.Array, seed) -> jax.Array:
    """Standard normal via Box-Muller on two decorrelated uniform streams.

    Element i uses streams (i, seed) and (i, seed ^ 0x5BF03635); both jnp and
    the Pallas kernel call this exact function body, so results are bit-equal.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    u1 = _u32_to_unit(hash_u32(idx, seed))
    u2 = _u32_to_unit(hash_u32(idx, seed ^ np.uint32(0x5BF03635)))
    r = jnp.sqrt(np.float32(-2.0) * jnp.log(u1))
    theta = np.float32(2.0 * np.pi) * u2
    return r * jnp.cos(theta)


def rademacher_from_index(idx: jax.Array, seed) -> jax.Array:
    bits = hash_u32(idx, seed)
    return jnp.where(bits & np.uint32(1), np.float32(1.0), np.float32(-1.0))


# ---------------------------------------------------------------------------
# Structured sketches: SRHT and CountSketch
# ---------------------------------------------------------------------------
#
# The Gaussian sketch costs O(m n s) to apply.  The structured families cut
# that without giving up the subspace-embedding property the range finder
# needs:
#
#   SRHT         Omega = D H[:, J] * sqrt(n_pad / s): random signs D, the
#                normalized Hadamard transform H, and a without-replacement
#                column sample J.  Applied fast (sign flip + FWHT + column
#                subsample) it costs O(m n log n); every entry is +-1/sqrt(s).
#   CountSketch  one +-1 per row at a hashed bucket column: applying it is a
#                signed segment-sum over A's columns — O(m n), no GEMM at all.
#
# Both are derived from the SAME counter RNG as the Gaussian sketch (distinct
# salted streams), so they are deterministic in (n, s, seed) and traceable
# (the seed may be a traced scalar — sampling uses hash + argsort, never a
# host RNG).

#: salts decorrelating the structured streams from the Gaussian one
_SRHT_SIGN_SALT = np.uint32(0x7F4A7C15)
_SRHT_SAMPLE_SALT = np.uint32(0x94D049BB)
_CS_SIGN_SALT = np.uint32(0xBF58476D)
_CS_BUCKET_SALT = np.uint32(0x2545F491)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def fwht(x: jax.Array) -> jax.Array:
    """Normalized fast Walsh-Hadamard transform along the LAST axis.

    The axis length must be a power of two; the result equals ``x @ H`` for
    the symmetric normalized Hadamard matrix (entries +-1/sqrt(n)), computed
    in O(n log n) butterflies instead of an O(n^2) GEMM."""
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError(f"FWHT length must be a power of two, got {n}")
    lead = x.shape[:-1]
    h = 1
    while h < n:
        y = x.reshape(lead + (n // (2 * h), 2, h))
        a, b = y[..., 0, :], y[..., 1, :]
        x = jnp.stack([a + b, a - b], axis=-2).reshape(lead + (n,))
        h *= 2
    return x * np.float32(1.0 / math.sqrt(n))


def srht_sample_cols(n_pad: int, s: int, seed) -> jax.Array:
    """The SRHT column sample J: `s` distinct Hadamard columns out of
    ``n_pad``, drawn by ranking counter-hash keys (deterministic in seed,
    traceable, without replacement)."""
    seed = jnp.asarray(seed, jnp.uint32)
    keys = hash_u32(jnp.arange(n_pad, dtype=jnp.uint32), seed ^ _SRHT_SAMPLE_SALT)
    return jnp.argsort(keys)[:s]


def srht_matrix(n: int, s: int, seed, dtype=jnp.float32) -> jax.Array:
    """Materialize the n x s SRHT Omega = D H[:, J] * sqrt(n_pad / s).

    ``H`` is the n_pad-point normalized Hadamard matrix (n_pad = next power
    of two >= n; the missing rows correspond to zero-padding A's columns, so
    truncation loses nothing).  Entry (i, j) is
    ``d_i * (-1)^popcount(i & J_j) / sqrt(s)`` — exactly the map the fast
    `apply_srht` path computes, materialized for operator (matrix-free)
    sources."""
    n_pad = _next_pow2(n)
    rows = jnp.arange(n, dtype=jnp.uint32)
    seed = jnp.asarray(seed, jnp.uint32)
    d = rademacher_from_index(rows, seed ^ _SRHT_SIGN_SALT)
    cols = srht_sample_cols(n_pad, s, seed).astype(jnp.uint32)
    parity = jax.lax.population_count(rows[:, None] & cols[None, :]) & 1
    signs = jnp.where(parity == 1, np.float32(-1.0), np.float32(1.0))
    return (d[:, None] * signs * np.float32(1.0 / math.sqrt(s))).astype(dtype)


def countsketch_buckets(n: int, s: int, seed) -> jax.Array:
    """Bucket assignment h: [n] -> [s], BALANCED by ranking hash keys (each
    bucket receives ceil(n/s) or floor(n/s) rows when n >= s).  A raw
    ``hash % s`` leaves a bucket empty with non-negligible probability at
    panel widths, which would hand the range finder an exactly-zero sketch
    column; the ranked assignment keeps every column populated."""
    seed = jnp.asarray(seed, jnp.uint32)
    keys = hash_u32(jnp.arange(n, dtype=jnp.uint32), seed ^ _CS_BUCKET_SALT)
    h = jnp.zeros((n,), jnp.int32)
    return h.at[jnp.argsort(keys)].set(jnp.arange(n, dtype=jnp.int32) % s)


def countsketch_matrix(n: int, s: int, seed, dtype=jnp.float32) -> jax.Array:
    """Materialize the n x s CountSketch Omega: row i holds a single +-1 at
    column h(i)."""
    seed = jnp.asarray(seed, jnp.uint32)
    signs = rademacher_from_index(jnp.arange(n, dtype=jnp.uint32),
                                  seed ^ _CS_SIGN_SALT)
    h = countsketch_buckets(n, s, seed)
    onehot = (h[:, None] == jnp.arange(s, dtype=jnp.int32)[None, :])
    return (signs[:, None] * onehot.astype(jnp.float32)).astype(dtype)


def apply_srht(A: jax.Array, s: int, seed) -> jax.Array:
    """Y = A @ Omega_srht via the fast path: sign-flip A's columns, FWHT
    (zero-padded to a power of two), subsample s columns — O(m n log n)
    instead of the O(m n s) GEMM.  Same linear map as
    ``A @ srht_matrix(n, s, seed)`` (different summation order)."""
    n = A.shape[-1]
    n_pad = _next_pow2(n)
    seed = jnp.asarray(seed, jnp.uint32)
    d = rademacher_from_index(jnp.arange(n, dtype=jnp.uint32),
                              seed ^ _SRHT_SIGN_SALT).astype(A.dtype)
    Ad = A * d[None, :]
    if n_pad > n:
        pad = [(0, 0)] * (A.ndim - 1) + [(0, n_pad - n)]
        Ad = jnp.pad(Ad, pad)
    H = fwht(Ad.astype(jnp.promote_types(A.dtype, jnp.float32)))
    cols = srht_sample_cols(n_pad, s, seed)
    return (H[..., cols] * np.float32(math.sqrt(n_pad) / math.sqrt(s))).astype(A.dtype)


def apply_countsketch(A: jax.Array, s: int, seed) -> jax.Array:
    """Y = A @ Omega_countsketch via a signed segment-sum over A's columns —
    O(m n), no GEMM.  Same linear map as ``A @ countsketch_matrix(...)``."""
    n = A.shape[-1]
    seed = jnp.asarray(seed, jnp.uint32)
    signs = rademacher_from_index(jnp.arange(n, dtype=jnp.uint32),
                                  seed ^ _CS_SIGN_SALT).astype(A.dtype)
    h = countsketch_buckets(n, s, seed)
    signed = jnp.moveaxis(A * signs[None, :], -1, 0)       # (n, ...)
    out = jax.ops.segment_sum(signed, h, num_segments=s)   # (s, ...)
    return jnp.moveaxis(out, 0, -1)


def apply_structured(A: jax.Array, s: int, seed, kind: str) -> jax.Array:
    """Fast application Y = A @ Omega for a structured sketch kind."""
    if kind == "srht":
        return apply_srht(A, s, seed)
    if kind == "countsketch":
        return apply_countsketch(A, s, seed)
    raise ValueError(f"not a structured sketch kind: {kind}")


# ---------------------------------------------------------------------------
# Materialized sketch matrices (host/oracle path)
# ---------------------------------------------------------------------------

SketchKind = Literal["gaussian", "rademacher", "srht", "countsketch"]

#: kinds applied by transform, not GEMM; the fused RNG+GEMM Pallas kernels
#: only generate the elementwise-i.i.d. kinds, so planners must not claim a
#: fused sketch for these
STRUCTURED_KINDS = ("srht", "countsketch")

#: every kind `sketch_matrix` accepts (config validation pins against this)
SKETCH_KINDS = ("gaussian", "rademacher") + STRUCTURED_KINDS


def sketch_matrix(
    n: int,
    s: int,
    seed: int,
    kind: SketchKind = "gaussian",
    dtype=jnp.float32,
    row_offset: int = 0,
) -> jax.Array:
    """Materialize the n x s sketch Omega.

    ``row_offset`` lets a row-sharded device generate *its* rows of the same
    global sketch (element (i, j) depends only on the global flat index
    i * s + j and the seed).  The structured kinds (srht / countsketch) are
    NOT row-decomposable — their sample/bucket draws need the global row
    count — so they reject a nonzero offset; the planner falls back to
    gaussian on the paths that stream panel-offset sketches."""
    if kind in STRUCTURED_KINDS:
        if row_offset:
            raise ValueError(
                f"sketch kind {kind!r} is not row-decomposable (its column "
                "sample / bucket assignment is global) — row_offset must be 0"
            )
        if kind == "srht":
            return srht_matrix(n, s, seed, dtype=dtype)
        return countsketch_matrix(n, s, seed, dtype=dtype)
    rows = jnp.arange(n, dtype=jnp.uint32)[:, None] + np.uint32(row_offset)
    cols = jnp.arange(s, dtype=jnp.uint32)[None, :]
    idx = rows * np.uint32(s) + cols
    if kind == "gaussian":
        vals = normal_from_index(idx, seed)
    elif kind == "rademacher":
        vals = rademacher_from_index(idx, seed)
    else:
        raise ValueError(f"unknown sketch kind: {kind}")
    return vals.astype(dtype)


@functools.partial(jax.jit, static_argnames=("s", "kind"))
def apply_sketch(A: jax.Array, s: int, seed, kind: SketchKind = "gaussian"):
    """C = A @ Omega with Omega materialized (reference path).

    The fused-no-materialization path lives in kernels/sketch_matmul.py.
    """
    n = A.shape[-1]
    omega = sketch_matrix(n, s, seed, kind, dtype=A.dtype)
    return A @ omega
