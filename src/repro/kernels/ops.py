"""JIT'd public wrappers for the Pallas kernels: padding, dtype policy, and
interpret-mode selection (on the CPU backend the kernels run in Pallas
interpret mode; on a TPU the same call sites compile them natively; any
other backend is refused).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune as _at
from repro.kernels import flash_attention as _fa
from repro.kernels import gram as _gram
from repro.kernels import matmul as _mm
from repro.kernels import power_step as _ps
from repro.kernels import sketch_matmul as _sm
from repro.kernels import spmm_sketch as _spmm
from repro.kernels import trsm as _trsm


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode on the CPU backend only.  On a TPU the kernels compile
    natively and a kernel the compiler refuses raises; any other backend
    raises here rather than silently interpreting."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run natively on a TPU or in interpret mode on the "
        f"CPU; backend {backend!r} is neither")


def _backend_name() -> str:
    """Autotune-cache namespace: execution mode PLUS the device kind.

    The device kind matters on both sides of the split: "interpret:cpu"
    timings can't shadow real-TPU winners, and winners recorded on one TPU
    generation (v5e) can't shadow another (v6e) — different VMEM/MXU
    envelopes want different tiles."""
    kind = jax.devices()[0].device_kind.lower().replace(" ", "-")
    return ("tpu" if _on_tpu() else "interpret") + f":{kind}"


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _block(dim: int, pref: int = 128) -> int:
    """Hardware-aligned block size: 128 where possible, else the padded dim."""
    return pref if dim >= pref else max(8, int(2 ** np.ceil(np.log2(max(dim, 1)))))


def _select_blocks(kernel: str, shape: tuple[int, ...], dtype) -> tuple[int, int, int]:
    """(bm, bn, bk) for a kernel call: the autotuner cache if it has an entry
    for this (shape-bucket, dtype, backend), else the 128 heuristic.

    Runs at trace time (pure Python over static shapes); `shape` is the
    logical problem shape (m, n, k) and tuned sizes are clamped per-dim so a
    cache entry recorded at a bigger bucket still yields a legal tiling.
    """
    m, n, k = shape
    tuned = _at.lookup(kernel, shape, jnp.dtype(dtype).name, _backend_name())
    if tuned is None:
        return _block(m), _block(n), _block(k)
    return _block(m, tuned.bm), _block(n, tuned.bn), _block(k, tuned.bk)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def matmul(x: jax.Array, y: jax.Array, out_dtype=None):
    """C = X @ Y via the tiled Pallas kernel (padded to MXU tiles)."""
    m, k = x.shape
    _, n = y.shape
    bm, bn, bk = _select_blocks("matmul", (m, n, k), x.dtype)
    xp = _pad_to(x, (bm, bk))
    yp = _pad_to(y, (bk, bn))
    out = _mm.matmul_padded(
        xp, yp, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype or x.dtype, interpret=_interpret(),
    )
    return out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("s", "kind", "out_dtype")
)
def sketch_matmul(
    a: jax.Array,
    s: int,
    seed=0,
    kind: str = "gaussian",
    out_dtype=None,
    row_offset=0,
):
    """C = A @ Omega[row_offset : row_offset + n, :s] with Omega generated
    inside the kernel.  ``row_offset=0`` is the monolithic sketch; a nonzero
    offset lets a column-panel of A consume its panel of the same logical
    Omega (blocked / out-of-core streaming).  ``row_offset`` AND ``seed``
    are traced (SMEM scalars) — panel streams, seed sweeps, and the batched
    vmap path all cost ONE kernel compile."""
    m, n = a.shape
    bm, bn, bk = _select_blocks("sketch_matmul", (m, s, n), a.dtype)
    bn = min(bn, _block(s))
    ap = _pad_to(a, (bm, bk))
    s_padded = s + (-s) % bn
    out = _sm.sketch_matmul_padded(
        ap, s, seed, s_padded=s_padded, kind=kind,
        bm=bm, bn=bn, bk=bk, out_dtype=out_dtype or a.dtype,
        interpret=_interpret(), row_offset=row_offset,
    )
    return out[:m, :s]


def spmm_blocks(shape: tuple[int, int], s: int, dtype) -> tuple[int, int]:
    """(bm, bk) tile shape for the block-ELL pack feeding `spmm_sketch`:
    the autotune cache's "spmm_sketch" entry for this (m, s, n) bucket if
    one exists (same `"<mode>:<device-kind>"` namespace as the dense
    kernels), else the 128-aligned heuristic.  Exposed separately because
    the PACK happens host-side in SparseOp, before any kernel call."""
    m, n = shape
    bm, _, bk = _select_blocks("spmm_sketch", (m, s, n), dtype)
    return bm, bk


@functools.partial(jax.jit, static_argnames=("s", "kind", "m", "out_dtype"))
def spmm_sketch(
    data: jax.Array,
    tilecols: jax.Array,
    s: int,
    seed=0,
    kind: str = "gaussian",
    *,
    m: int,
    out_dtype=None,
):
    """Y = A @ Omega for a block-ELL packed sparse A (`pack_block_ell`),
    with Omega tiles generated in VMEM per occupied tile — A's zero blocks
    are never read and Omega never exists in HBM.  ``m`` is the logical row
    count (the pack pads to block multiples); ``seed`` is traced."""
    sp = s + (-s) % _block(s)
    out = _spmm.spmm_sketch_padded(
        data, tilecols, s, seed, s_padded=sp, kind=kind,
        out_dtype=out_dtype or data.dtype, interpret=_interpret(),
    )
    return out[:m, :s]


@functools.partial(jax.jit, static_argnames=("s", "kind", "out_dtype"))
def sketch_gram(
    a: jax.Array,
    s: int,
    seed=0,
    kind: str = "gaussian",
    out_dtype=None,
    row_offset=0,
):
    """(Y, G) = (A @ Omega, Yᵀ Y) in ONE pass over A: the fused sketch with
    a Gram epilogue, so CholeskyQR's first Gram costs no extra pass over Y.
    G is fp32.  ``seed`` / ``row_offset`` are traced, as in `sketch_matmul`."""
    m, n = a.shape
    bm, _, bk = _select_blocks("sketch_gram", (m, s, n), a.dtype)
    ap = _pad_to(a, (bm, bk))
    s_padded = s + (-s) % _block(s)
    y, g = _sm.sketch_gram_padded(
        ap, s, seed, s_padded=s_padded, kind=kind,
        bm=bm, bk=bk, out_dtype=out_dtype or a.dtype,
        interpret=_interpret(), row_offset=row_offset,
    )
    return y[:m, :s], g[:s, :s]


@functools.partial(jax.jit, static_argnames=("s", "kind", "out_dtype"))
def sketch_power(
    a: jax.Array,
    s: int,
    seed=0,
    kind: str = "gaussian",
    out_dtype=None,
):
    """(Y, W, G) = (A @ Omega, Aᵀ Y, Yᵀ Y) in ONE pass over A: the fused
    RNG sketch through the power-step strip layout, so the stabilized
    one-pass range finder starts with W = AᵀY already accumulated."""
    m, n = a.shape
    bm, _, _ = _select_blocks("power_step", (m, n, s), a.dtype)
    nlane = _block(n)
    ap = _pad_to(a, (bm, nlane))
    sp = s + (-s) % _block(s)
    y, w, g = _ps.sketch_power_padded(
        ap, s, seed, s_padded=sp, kind=kind, bm=bm,
        out_dtype=out_dtype or a.dtype, interpret=_interpret(),
    )
    return y[:m, :s], w[:n, :s], g[:s, :s]


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def gram(y: jax.Array, out_dtype=jnp.float32):
    """G = Y^T Y via the symmetric (SYRK-style) kernel."""
    m, s = y.shape
    _, bs, bk = _select_blocks("gram", (s, s, m), y.dtype)
    bs = min(bs, _block(s))
    yp = _pad_to(y, (bk, bs))
    upper = _gram.gram_padded(yp, bs=bs, bk=bk, out_dtype=out_dtype, interpret=_interpret())
    full = _gram.symmetrize_upper(upper, bs=bs)
    return full[:s, :s]


@functools.partial(jax.jit, static_argnames=("with_gram", "out_dtype"))
def power_step(a: jax.Array, x: jax.Array, with_gram: bool = False, out_dtype=None):
    """(Y, Z[, G]) = (A @ X, Aᵀ @ Y[, Yᵀ Y]) — the fused two-sided power
    step: each A tile is read once per pass (see kernels/power_step.py).

    ``a`` is A (m x n, tall), ``x`` is X (n x s, sketch-width)."""
    m, n = a.shape
    _, s = x.shape
    bm, _, _ = _select_blocks("power_step", (m, n, s), a.dtype)
    sp = _block(s)
    nlane = _block(n)
    ap = _pad_to(a, (bm, nlane))
    xp = _pad_to(x, (nlane, sp))
    outs = _ps.power_step_padded(
        ap, xp, bm=bm, out_dtype=out_dtype or a.dtype,
        with_gram=with_gram, interpret=_interpret(),
    )
    if with_gram:
        y, z, g = outs
        return y[:m, :s], z[:n, :s], g[:s, :s]
    y, z = outs
    return y[:m, :s], z[:n, :s]


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def tri_solve_right(y: jax.Array, r: jax.Array, out_dtype=None):
    """Q = Y R⁻¹ for upper-triangular R via the tiled TRSM kernel
    (forward substitution over column blocks, inverted diagonal blocks)."""
    m, s = y.shape
    bm, bs, _ = _select_blocks("trsm", (m, s, s), y.dtype)
    bs = min(bs, _block(s))
    yp = _pad_to(y, (bm, bs))
    sp = yp.shape[1]
    rp = jnp.zeros((sp, sp), r.dtype).at[:s, :s].set(r)
    if sp > s:
        # identity on the padded diagonal keeps every block invertible
        pad_diag = jnp.arange(sp) >= s
        rp = rp + jnp.diag(pad_diag.astype(r.dtype))
    dinv = _trsm.invert_diag_blocks(rp, bs)
    q = _trsm.tri_solve_right_padded(
        yp, rp, dinv, bm=bm, bs=bs,
        out_dtype=out_dtype or y.dtype, interpret=_interpret(),
    )
    return q[:m, :s]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
):
    """Flash attention. q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D]."""
    B, Hq, Tq, D = q.shape
    Tk = k.shape[2]
    bq = _block(Tq)
    bk = _block(Tk)
    qp = _pad_to(q, (1, 1, bq, D))
    kp = _pad_to(k, (1, 1, bk, D))
    vp = _pad_to(v, (1, 1, bk, D))
    out = _fa.flash_attention_padded(
        qp, kp, vp, tq=Tq, tk=Tk, causal=causal, window=window,
        softcap=softcap, scale=scale, bq=bq, bk=bk, interpret=_interpret(),
    )
    return out[:, :, :Tq, :]
