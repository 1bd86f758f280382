"""Tiled right-triangular solve: Q = Y R⁻¹ for upper-triangular R (s x s).

This is the CholeskyQR "apply" step (BLAS TRSM, side=right).  The GPU-BLAS
formulation (also cuBLAS's): invert only the small diagonal blocks of R on
the host (s is the sketch width, so each block is a tiny triangular solve
against I), then the whole solve becomes a short sequence of GEMMs per row
strip — forward substitution over column blocks:

  Q_c = (Y_c − Σ_{c'<c} Q_{c'} R_{c',c}) · inv(R_{c,c})

Row strips are independent, so the grid is (i) over bm-row strips; R and
the inverted diagonal blocks have constant index maps (fetched once for the
whole grid).  The column-block loop is a static Python loop — the number of
column blocks is s_padded / bs, at most a handful for sketch widths.

Numerics: the diagonal blocks inherit R's conditioning (kappa(R) = kappa(Y)
for a CholeskyQR factor), so the block-inverse is as stable as the TRSM it
replaces at first order; CQR2's second pass restores O(eps) orthogonality
either way (Yamamoto et al. 2015).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _trsm_kernel(y_ref, r_ref, dinv_ref, q_ref, *, nc, bs):
    rf = r_ref[...].astype(jnp.float32)
    for c in range(nc):  # static: nc = s_padded // bs, small
        lo = c * bs
        acc = y_ref[:, lo : lo + bs].astype(jnp.float32)
        for cp in range(c):
            lo_p = cp * bs
            # Q blocks already written this grid step are VMEM-resident.
            acc -= jnp.dot(
                q_ref[:, lo_p : lo_p + bs].astype(jnp.float32),
                rf[lo_p : lo_p + bs, lo : lo + bs],
                preferred_element_type=jnp.float32,
            )
        qc = jnp.dot(
            acc,
            dinv_ref[c].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        q_ref[:, lo : lo + bs] = qc.astype(q_ref.dtype)


def invert_diag_blocks(r: jax.Array, bs: int) -> jax.Array:
    """[nc, bs, bs] inverses of R's diagonal blocks (host-side, tiny solves).

    R must be padded to a multiple of bs with IDENTITY on the padded
    diagonal (the ops.py wrapper does this) so every block is invertible.
    """
    s = r.shape[0]
    nc = s // bs
    blocks = jnp.stack([r[c * bs : (c + 1) * bs, c * bs : (c + 1) * bs] for c in range(nc)])
    eye = jnp.eye(bs, dtype=r.dtype)
    return jax.vmap(lambda b: jax.scipy.linalg.solve_triangular(b, eye, lower=False))(blocks)


def tri_solve_right_padded(
    y: jax.Array,
    r: jax.Array,
    dinv: jax.Array,
    *,
    bm: int = 128,
    bs: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Q = Y R⁻¹ for block-padded Y (m x s), upper-triangular R (s x s)."""
    m, s = y.shape
    assert m % bm == 0 and s % bs == 0 and r.shape == (s, s)
    nc = s // bs
    out_dtype = out_dtype or y.dtype
    kernel = functools.partial(_trsm_kernel, nc=nc, bs=bs)
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, s), lambda i: (i, 0)),
            pl.BlockSpec((s, s), lambda i: (0, 0)),
            pl.BlockSpec((nc, bs, bs), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, s), out_dtype),
        interpret=interpret,
        name="trsm",
    )(y, r, dinv)
