"""Fused RNG + SpMM sketch kernel: Y = A @ Omega for SPARSE A.

The dense fused sketch (kernels/sketch_matmul.py) reads every A element; for
a sparse A that wastes (1 - density) of the traffic.  Here A is packed once
into a block-ELL layout — for each bm-row block, the list of (bm x bk) tiles
that contain at least one nonzero, stored dense and zero-padded to the
longest list — and the kernel walks only those tiles.  The tile's matching
(bk x s) Omega slab is generated in VMEM from the SAME counter RNG as the
dense kernels (`_omega_tile`, bit-identical to core/sketch.py), keyed by the
tile's column id, so Omega never exists in HBM and A's zero blocks are never
read.

HBM traffic: ~nnz * (value + index) for A (plus block padding) + the m x s
output — the roofline model's `spmm_sketch_bytes` (repro/roofline/rsvd_model).
The pack is host-side numpy, cached per tile shape by SparseOp; matrices
whose padding would exceed the `max_fill` fraction of the dense footprint
are rejected (None) and take the materialized-Omega BCOO path instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sketch_matmul import _omega_tile


def pack_block_ell(bcoo, bm: int, bk: int, max_fill: float | None = None):
    """Pack a 2-D BCOO into block-ELL tiles for `spmm_sketch_padded`.

    Returns ``(data, tilecols)`` — data [nrb, nt, bm, bk] holds the dense
    tiles (zero-padded; nt = max occupied tiles over row blocks), tilecols
    [nrb, nt] int32 holds each tile's COLUMN-BLOCK id (padding slots point
    at block 0 with all-zero data, contributing nothing).  Returns None when
    the padded tile footprint exceeds ``max_fill * m * n`` — the matrix is
    too dense / too scattered for the tiled kernel to beat a dense read.

    Host-side numpy (runs once per (bm, bk), cached by SparseOp); duplicate
    coordinates are summed, out-of-range padding indices dropped.
    """
    m, n = bcoo.shape
    rows = np.asarray(bcoo.indices[:, 0], dtype=np.int64)
    cols = np.asarray(bcoo.indices[:, 1], dtype=np.int64)
    vals = np.asarray(bcoo.data)
    keep = (rows >= 0) & (rows < m) & (cols >= 0) & (cols < n)
    rows, cols, vals = rows[keep], cols[keep], vals[keep]

    nrb = -(-m // bm)
    ncb = -(-n // bk)
    rb, cb = rows // bm, cols // bk
    tile_id = rb * ncb + cb
    uniq, inv = np.unique(tile_id, return_inverse=True)
    uniq_rb = uniq // ncb
    counts = np.bincount(uniq_rb, minlength=nrb)
    nt = max(int(counts.max()) if uniq.size else 0, 1)
    if max_fill is not None and nrb * nt * bm * bk > max_fill * m * n:
        return None

    # slot of each occupied tile within its row block: uniq is sorted, so
    # tiles of one row block are contiguous — rank minus the block's start
    first = np.searchsorted(uniq_rb, np.arange(nrb), side="left")
    slot = np.arange(uniq.size) - first[uniq_rb]

    data = np.zeros((nrb, nt, bm, bk), dtype=vals.dtype)
    tilecols = np.zeros((nrb, nt), dtype=np.int32)
    tilecols[uniq_rb, slot] = (uniq % ncb).astype(np.int32)
    np.add.at(data, (rb, slot[inv], rows % bm, cols % bk), vals)
    return jnp.asarray(data), jnp.asarray(tilecols)


def _spmm_sketch_kernel(cols_ref, seed_ref, data_ref, o_ref, acc_ref,
                        *, nt, bk, sp, s, kind):
    i, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # this tile holds A columns [c*bk, (c+1)*bk) -> Omega rows of the same
    # range; generate that slab in VMEM, keyed by the prefetched tile id
    row0 = cols_ref[i * nt + t].astype(jnp.uint32) * np.uint32(bk)
    omega = _omega_tile(row0, jnp.uint32(0), bk, sp, s, seed_ref[0], kind)
    acc_ref[...] += jnp.dot(
        data_ref[0, 0].astype(jnp.float32), omega,
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == nt - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def spmm_sketch_padded(
    data: jax.Array,
    tilecols: jax.Array,
    s: int,
    seed,
    *,
    s_padded: int,
    kind: str = "gaussian",
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Y = A @ Omega over a block-ELL packed A (`pack_block_ell`).

    Grid (nrb, nt): row block i accumulates its nt tile products into a
    VMEM scratch and flushes once — Y's block row is written exactly once.
    `s` is the LOGICAL sketch width (the RNG flat index uses it, so results
    are independent of padding); columns >= s of the padded output are
    garbage the caller slices off.  Zero-padded tiles multiply a valid Omega
    slab by zeros, so they are numerically inert.  The tile ids (flattened)
    and ``seed`` are scalar-prefetched into SMEM — the seed is traced, so
    seed sweeps share one compiled program, as in the dense kernels.
    """
    nrb, nt, bm, bk = data.shape
    out_dtype = out_dtype or data.dtype
    kernel = functools.partial(
        _spmm_sketch_kernel, nt=nt, bk=bk, sp=s_padded, s=s, kind=kind
    )
    sd = jnp.asarray(seed, jnp.uint32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nrb, nt),
            in_specs=[pl.BlockSpec((1, 1, bm, bk),
                                   lambda i, t, cols, sd: (i, t, 0, 0))],
            out_specs=pl.BlockSpec((bm, s_padded), lambda i, t, cols, sd: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bm, s_padded), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nrb * bm, s_padded), out_dtype),
        interpret=interpret,
        name="spmm_sketch",
    )(tilecols.reshape(-1), sd, data)
