"""Fused two-sided power-step kernel: one pass over A per power iteration.

The unfused power iteration pays two full passes over the tall matrix per
step — ``Y = A @ X`` reads A (m·n), then ``Z = A.T @ Y`` reads A again.  Lu
et al. (arXiv:1706.07191) restructure the out-of-core block rSVD so every
pass over A does maximal work; this kernel is that idea on Pallas tiles:

  grid (i) over row strips of A (bm x n each).  Per strip:
    Y_i = A_i @ X            (bm x s)   — written to the Y output
    Z  += A_i^T @ Y_i        (n  x s)   — VMEM accumulator, flushed at the end
    G  += Y_i^T @ Y_i        (s  x s)   — optional Gram epilogue (free: Y_i
                                          is still VMEM-resident)

so each A tile is read ONCE and the step yields Y = A·X, Z = Aᵀ(A·X), and
(optionally) G = YᵀY.  The stabilized scheme consumes all three: with
CholeskyQR, Q = Y R⁻¹ means AᵀQ = Z R⁻¹ — Q never has to be re-multiplied
against A, and the first CQR Gram comes out of the epilogue.  The final
projection B = QᵀA = R⁻ᵀ Zᵀ also falls out of the last step's Z, so the
whole post-sketch rSVD does exactly one pass over A per power iteration.

HBM bytes per power step (fp32, the DESIGN.md §2 table):
  unfused   2·m·n + 3·m·s + 2·n·s      (two A passes + Y/Q round-trips)
  fused       m·n +   m·s + 2·n·s      (one A pass; G rides along)

VMEM working set per grid step: the (bm x n) A strip + X + the Z
accumulator (both n x s).  X and Z have constant index maps, so they are
fetched/flushed once for the whole grid, not per strip.  The kernels ask
the compiler for the core's whole VMEM (`hw.VMEM_BYTES`), and the fusion
gate (`fused_power_vmem_bytes`) checks the working set against that same
number, so a plan that claims fusion is one the chip's compiler accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.roofline import hw

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=hw.VMEM_BYTES)

#: rows of Ω generated per step of the sketch kernel's fill loop — bounds
#: the RNG temporaries to a (chunk x s) tile instead of the whole (n x s) Ω
_OMEGA_CHUNK = 128


def fused_power_vmem_bytes(n: int, s: int, bm: int = 128, dtype_bytes: int = 4) -> int:
    """VMEM the strip kernels allocate at padded (n, s), counted high.

    Every pipelined block is double-buffered: the (bm x n) A strip, the
    (bm x s) Y block, the (n x s) X input (power_step) and Z output, and
    the (s x s) G output.  Scratch: the (n x s) Ω or Z accumulator pair and
    the G accumulator.  Temporaries: the f32 strip and its transpose, the
    (n x s) AᵀY product, the Y tile, and the RNG's (chunk x s) tiles.
    Callers (core/rsvd.py, the planner) fuse only when this fits
    `hw.VMEM_BYTES`, the limit the kernels compile with."""
    f32 = 4
    ns, ss = n * s, s * s
    blocks = 2 * (bm * n * dtype_bytes + bm * s * dtype_bytes
                  + 2 * ns * dtype_bytes + ss * f32)
    scratch = 2 * ns * f32 + ss * f32
    temps = 2 * bm * n * f32 + ns * f32 + bm * s * f32 + 8 * _OMEGA_CHUNK * s * f32
    return blocks + scratch + temps


def _power_step_kernel(a_ref, x_ref, y_ref, z_ref, zacc_ref, *, ni):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    af = a_ref[...].astype(jnp.float32)
    y = jnp.dot(af, x_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    zacc_ref[...] += jnp.dot(af.T, y, preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _flush():
        z_ref[...] = zacc_ref[...].astype(z_ref.dtype)


def _power_step_gram_kernel(a_ref, x_ref, y_ref, z_ref, g_ref, zacc_ref, gacc_ref, *, ni):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        zacc_ref[...] = jnp.zeros_like(zacc_ref)
        gacc_ref[...] = jnp.zeros_like(gacc_ref)

    af = a_ref[...].astype(jnp.float32)
    y = jnp.dot(af, x_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    zacc_ref[...] += jnp.dot(af.T, y, preferred_element_type=jnp.float32)
    gacc_ref[...] += jnp.dot(y.T, y, preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _flush():
        z_ref[...] = zacc_ref[...].astype(z_ref.dtype)
        g_ref[...] = gacc_ref[...].astype(g_ref.dtype)


def _sketch_power_kernel(
    seed_ref, a_ref, y_ref, z_ref, g_ref, omega_ref, zacc_ref, gacc_ref,
    *, ni, s, sp, kind,
):
    """power_step with X = Omega generated in VMEM from the counter RNG.

    Omega is generated ONCE (first grid step) into a persistent VMEM scratch
    and reused by every strip, so the sketch pass yields Y = A·Ω, W = AᵀY,
    and G = YᵀY from a single read of A — the stabilized fused path starts
    its first power iteration with W already in hand (reads of A for the
    whole rSVD: 1 + q, the DESIGN.md §2 claim)."""
    from repro.kernels.sketch_matmul import _omega_tile

    i = pl.program_id(0)
    n_p = omega_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        chunk = min(_OMEGA_CHUNK, n_p)

        def fill(c, carry):
            r0 = pl.multiple_of(c * chunk, chunk)
            omega_ref[pl.ds(r0, chunk), :] = _omega_tile(
                r0.astype(jnp.uint32), jnp.uint32(0), chunk, sp, s,
                seed_ref[0, 0], kind,
            )
            return carry

        jax.lax.fori_loop(0, n_p // chunk, fill, 0)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)
        gacc_ref[...] = jnp.zeros_like(gacc_ref)

    af = a_ref[...].astype(jnp.float32)
    y = jnp.dot(af, omega_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    zacc_ref[...] += jnp.dot(af.T, y, preferred_element_type=jnp.float32)
    gacc_ref[...] += jnp.dot(y.T, y, preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _flush():
        z_ref[...] = zacc_ref[...].astype(z_ref.dtype)
        g_ref[...] = gacc_ref[...].astype(g_ref.dtype)


def sketch_power_padded(
    a: jax.Array,
    s: int,
    seed,
    *,
    s_padded: int,
    kind: str = "gaussian",
    bm: int = 128,
    out_dtype=None,
    interpret: bool = False,
):
    """(Y, W, G) = (A Ω, Aᵀ Y, Yᵀ Y) with Ω generated in VMEM — one pass.

    Padded Ω rows (>= n) produce finite garbage but multiply zero-padded A
    columns; padded Ω columns (>= s) produce garbage Y/W/G columns the
    wrapper slices off."""
    m, n = a.shape
    assert m % bm == 0
    ni = m // bm
    out_dtype = out_dtype or a.dtype
    kernel = functools.partial(
        _sketch_power_kernel, ni=ni, s=s, sp=s_padded, kind=kind
    )
    assert n % min(_OMEGA_CHUNK, n) == 0
    sd = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    return pl.pallas_call(
        kernel,
        grid=(ni,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, s_padded), lambda i: (i, 0)),
            pl.BlockSpec((n, s_padded), lambda i: (0, 0)),
            pl.BlockSpec((s_padded, s_padded), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, s_padded), out_dtype),
            jax.ShapeDtypeStruct((n, s_padded), out_dtype),
            jax.ShapeDtypeStruct((s_padded, s_padded), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, s_padded), jnp.float32),
            pltpu.VMEM((n, s_padded), jnp.float32),
            pltpu.VMEM((s_padded, s_padded), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="sketch_power",
    )(sd, a)


def power_step_padded(
    a: jax.Array,
    x: jax.Array,
    *,
    bm: int = 128,
    out_dtype=None,
    with_gram: bool = False,
    interpret: bool = False,
):
    """(Y, Z[, G]) = (A @ X, Aᵀ @ Y[, Yᵀ Y]) for block-padded A (m x n), X (n x s).

    One read of each A tile; Z and G live in VMEM accumulators across the
    whole strip grid and are flushed once.  Padded rows/cols of A are zero,
    so logical regions of Y/Z/G are uncontaminated (padding of X likewise
    must be zero — the ops.py wrapper guarantees it).
    """
    m, n = a.shape
    n2, s = x.shape
    assert n == n2 and m % bm == 0
    ni = m // bm
    out_dtype = out_dtype or a.dtype
    if with_gram:
        kernel = functools.partial(_power_step_gram_kernel, ni=ni)
        out_specs = [
            pl.BlockSpec((bm, s), lambda i: (i, 0)),
            pl.BlockSpec((n, s), lambda i: (0, 0)),
            pl.BlockSpec((s, s), lambda i: (0, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((m, s), out_dtype),
            jax.ShapeDtypeStruct((n, s), out_dtype),
            jax.ShapeDtypeStruct((s, s), jnp.float32),
        ]
        scratch = [
            pltpu.VMEM((n, s), jnp.float32),
            pltpu.VMEM((s, s), jnp.float32),
        ]
    else:
        kernel = functools.partial(_power_step_kernel, ni=ni)
        out_specs = [
            pl.BlockSpec((bm, s), lambda i: (i, 0)),
            pl.BlockSpec((n, s), lambda i: (0, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((m, s), out_dtype),
            jax.ShapeDtypeStruct((n, s), out_dtype),
        ]
        scratch = [pltpu.VMEM((n, s), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(ni,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((n, s), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="power_step",
    )(a, x)
