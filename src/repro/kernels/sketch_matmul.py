"""Fused RNG + GEMM sketch kernel: C = A @ Omega with Omega generated in VMEM.

The paper materializes the Gaussian sketch with cuRAND and then runs a GEMM
— two passes over HBM for Omega (write, then read).  At sketch width s << n
the GEMM A @ Omega is *memory-bound*, so on TPU we fuse: each (bk x bn)
Omega tile is generated directly in VMEM from the counter-based RNG
(murmur3-finalizer hash + Box-Muller, bit-identical to core/sketch.py) inside
the reduction loop, so Omega never exists in HBM at all.

HBM traffic: paper scheme reads A (m*n) + writes/reads Omega (2*n*s);
fused scheme reads A only.  This is the 'beyond-paper' optimization whose
roofline effect is recorded in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)
_STREAM2 = np.uint32(0x5BF03635)


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def _hash_u32(idx, seed):
    h = _fmix(idx * _GOLDEN + seed)
    h = _fmix(h ^ (seed * _M1 + np.uint32(0x27220A95)))
    return h


def _u32_to_unit(bits):
    # via int32: the value is < 2^24, so the result is exact, and Mosaic
    # has no uint32 -> float32 cast
    return (bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * np.float32(
        1.0 / 16777216.0
    ) + np.float32(1.0 / 16777216.0)


def _omega_tile(row0, col0, bk, bn, s, seed, kind):
    """Generate the (bk x bn) Omega tile starting at (row0, col0) in VMEM.

    Matches core.sketch element-for-element: element (r, c) is a function of
    the flat index r * s + c only.
    """
    rows = row0 + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
    idx = rows * np.uint32(s) + cols
    seed_u = jnp.asarray(seed, jnp.uint32)
    if kind == "gaussian":
        u1 = _u32_to_unit(_hash_u32(idx, seed_u))
        u2 = _u32_to_unit(_hash_u32(idx, seed_u ^ _STREAM2))
        r = jnp.sqrt(np.float32(-2.0) * jnp.log(u1))
        theta = np.float32(2.0 * np.pi) * u2
        return r * jnp.cos(theta)
    if kind == "rademacher":
        bits = _hash_u32(idx, seed_u)
        return jnp.where(bits & np.uint32(1), np.float32(1.0), np.float32(-1.0))
    raise ValueError(kind)


def _sketch_kernel(off_ref, seed_ref, a_ref, o_ref, acc_ref, *, nk, bk, bn, s, kind):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row0 = (kk * bk).astype(jnp.uint32) + off_ref[0, 0]
    col0 = (pl.program_id(1) * bn).astype(jnp.uint32)
    omega = _omega_tile(row0, col0, bk, bn, s, seed_ref[0, 0], kind)
    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32), omega, preferred_element_type=jnp.float32
    )

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sketch_matmul_padded(
    a: jax.Array,
    s: int,
    seed: int,
    *,
    s_padded: int,
    kind: str = "gaussian",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    row_offset: int = 0,
) -> jax.Array:
    """C = A @ Omega for A already padded to (m, k) block multiples.

    `s` is the LOGICAL sketch width (used in the flat RNG index so results
    are independent of padding); `s_padded` is the padded output width.
    Padded Omega columns (>= s) produce finite garbage that the caller
    slices off; padded A rows are zero so they contribute nothing.

    `row_offset` shifts the RNG row index: the kernel consumes rows
    [row_offset, row_offset + k) of the logical Omega, so a column-panel
    of A streamed in a separate call regenerates ITS panel of the same
    global sketch bit-identically (the out-of-core / blocked contract,
    mirroring ``core.sketch.sketch_matrix(row_offset=...)``).  Both
    `row_offset` AND `seed` are TRACED scalars (SMEM operands), so panel
    streams, seed sweeps, GaLore refreshes, and the batched vmap path all
    share ONE compiled program.
    """
    m, k = a.shape
    assert m % bm == 0 and k % bk == 0 and s_padded % bn == 0
    nk = k // bk
    out_dtype = out_dtype or a.dtype
    kernel = functools.partial(
        _sketch_kernel, nk=nk, bk=bk, bn=bn, s=s, kind=kind
    )
    off = jnp.asarray(row_offset, jnp.uint32).reshape(1, 1)
    sd = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, s_padded // bn, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s_padded), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="sketch_matmul",
    )(off, sd, a)


# ---------------------------------------------------------------------------
# Sketch + Gram epilogue: Y = A @ Omega and G = Y^T Y in ONE pass over A
# ---------------------------------------------------------------------------

def _sketch_gram_kernel(
    off_ref, seed_ref, a_ref, y_ref, g_ref, yacc_ref, gacc_ref,
    *, ni, nk, bk, sp, s, kind,
):
    i, kk = pl.program_id(0), pl.program_id(1)

    @pl.when(kk == 0)
    def _init_y():
        yacc_ref[...] = jnp.zeros_like(yacc_ref)

    @pl.when((i == 0) & (kk == 0))
    def _init_g():
        gacc_ref[...] = jnp.zeros_like(gacc_ref)

    row0 = (kk * bk).astype(jnp.uint32) + off_ref[0, 0]
    omega = _omega_tile(row0, jnp.uint32(0), bk, sp, s, seed_ref[0, 0], kind)
    yacc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32), omega, preferred_element_type=jnp.float32
    )

    @pl.when(kk == nk - 1)
    def _row_done():
        y = yacc_ref[...]
        y_ref[...] = y.astype(y_ref.dtype)
        # Gram epilogue: Y's block row is complete and still resident in
        # VMEM — accumulate its contribution to G = Y^T Y with no extra
        # pass over Y (CQR's first Gram rides along for free).
        gacc_ref[...] += jnp.dot(y.T, y, preferred_element_type=jnp.float32)

    @pl.when((i == ni - 1) & (kk == nk - 1))
    def _flush_g():
        g_ref[...] = gacc_ref[...].astype(g_ref.dtype)


def sketch_gram_padded(
    a: jax.Array,
    s: int,
    seed,
    *,
    s_padded: int,
    kind: str = "gaussian",
    bm: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
    row_offset=0,
):
    """(Y, G) = (A @ Omega, Y^T Y) with Omega generated in VMEM — one pass.

    The sketch width is held as a single block (``s_padded`` columns, no j
    grid axis), so the completed (bm x s_padded) block row of Y is resident
    when its Gram contribution is accumulated; sketch widths are small
    (s = k + oversampling), so this fits VMEM comfortably.  G is fp32 and
    includes padded columns (garbage that the wrapper slices off); logical
    entries are uncontaminated because padded A rows/cols are zero.
    """
    m, k = a.shape
    assert m % bm == 0 and k % bk == 0
    ni, nk = m // bm, k // bk
    out_dtype = out_dtype or a.dtype
    kernel = functools.partial(
        _sketch_gram_kernel, ni=ni, nk=nk, bk=bk, sp=s_padded, s=s, kind=kind
    )
    off = jnp.asarray(row_offset, jnp.uint32).reshape(1, 1)
    sd = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    return pl.pallas_call(
        kernel,
        grid=(ni, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, kk: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, kk: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, kk: (i, kk)),
        ],
        out_specs=[
            pl.BlockSpec((bm, s_padded), lambda i, kk: (i, 0)),
            pl.BlockSpec((s_padded, s_padded), lambda i, kk: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, s_padded), out_dtype),
            jax.ShapeDtypeStruct((s_padded, s_padded), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, s_padded), jnp.float32),
            pltpu.VMEM((s_padded, s_padded), jnp.float32),
        ],
        interpret=interpret,
        name="sketch_gram",
    )(off, sd, a)
