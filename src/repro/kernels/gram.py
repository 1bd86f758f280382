"""Symmetric Gram matrix kernel: G = Y^T Y (the CholeskyQR hot spot).

Exploits symmetry: only output blocks with j >= i are computed on the MXU
(upper block triangle); lower blocks are written as zeros and the wrapper
reconstructs G = U + U^T - diag(diag(U)).  This halves the MXU work versus a
generic matmul — the SYRK-vs-GEMM trick of BLAS, restated for Pallas tiles.

Grid (i, j, kk) over (S/bs, S/bs, M/bk); the reduction over the tall
dimension m is innermost with a VMEM fp32 accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(yl_ref, yr_ref, o_ref, acc_ref, *, nk: int):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j >= i)  # upper block-triangle only: SYRK saving
    def _mxu():
        acc_ref[...] += jnp.dot(
            yl_ref[...].astype(jnp.float32).T,
            yr_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _left_index(i, j, kk):
    # Pruned (j < i) sweeps clamp the reduction index to 0 so the whole
    # skipped kk sweep maps onto ONE already-resident block: consecutive
    # grid steps with an unchanged block index issue no DMA, so the lower
    # triangle costs at most one fetch per (i, j) cell instead of nk.
    k_eff = jnp.where(j < i, 0, kk)
    return (k_eff, i)


def _right_index(i, j, kk):
    k_eff = jnp.where(j < i, 0, kk)
    j_eff = jnp.where(j < i, i, j)  # also pin the column: constant across the skipped prefix j = 0..i-1
    return (k_eff, j_eff)


def gram_padded(
    y: jax.Array,
    *,
    bs: int = 128,
    bk: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Upper-triangular (block-wise) part of Y^T Y; wrapper symmetrizes."""
    m, s = y.shape
    assert m % bk == 0 and s % bs == 0
    nk = m // bk
    kernel = functools.partial(_gram_kernel, nk=nk)
    upper = pl.pallas_call(
        kernel,
        grid=(s // bs, s // bs, nk),
        in_specs=[
            # left operand: block column i of Y (transposed in-kernel)
            pl.BlockSpec((bk, bs), _left_index),
            # right operand: block column j of Y
            pl.BlockSpec((bk, bs), _right_index),
        ],
        out_specs=pl.BlockSpec((bs, bs), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s, s), out_dtype),
        scratch_shapes=[pltpu.VMEM((bs, bs), jnp.float32)],
        interpret=interpret,
        name="gram",
    )(y, y)
    return upper


def symmetrize_upper(upper: jax.Array, bs: int = 128) -> jax.Array:
    """Reconstruct full G from the block-upper-triangular kernel output.

    Off-diagonal *blocks* below the diagonal are zero; diagonal blocks are
    full (they were computed entirely).  So G = U + U^T - D where D is the
    block-diagonal part (counted twice by U + U^T).
    """
    s = upper.shape[0]
    blk = jnp.arange(s) // bs
    block_diag_mask = blk[:, None] == blk[None, :]
    block_diag = jnp.where(block_diag_mask, upper, jnp.zeros((), upper.dtype))
    return upper + upper.T - block_diag
