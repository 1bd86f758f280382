"""Randomized k-SVD — the paper's Algorithm 1, faithful and optimized paths.

Faithful path (defaults mirror the paper / cuSOLVER ``gesvdr`` semantics):

  1. draw Gaussian sketch Omega in R^{n x s},   s = k + oversampling
  2. Y = (A A^T)^q A Omega                      (chain of GEMMs)
  3. Q = QR(Y).Q                                (orthonormal range basis)
  4. B = Q^T A                                  (GEMM)
  5. B = U S V^T                                (small SVD, s x n)
  6. U~ = Q U                                   (GEMM)
  -> A_k ~= U~[:, :k] S[:k] V[:k, :]^T

Optimized (beyond-paper, TPU-native) switches — see DESIGN.md §2:
  * qr_method='cqr2'        CholeskyQR2 instead of Householder QR (BLAS-3)
  * small_svd='gram_jacobi' Gram + parallel-order Jacobi instead of LAPACK
  * power_scheme='stabilized'  re-orthonormalized subspace iteration
  * fused sketch            kernels/sketch_matmul.py generates Omega in VMEM

`randomized_eigvals` implements the paper's "only the k largest eigenvalues"
mode (steps 1-5, Sigma only), used in the PCA / spectra experiments.

Each step of the bodies below runs under a `jax.named_scope` (`rsvd.sketch`,
`rsvd.power`, `rsvd.orth`, `rsvd.project`, `rsvd.small_svd`,
`rsvd.assemble`): the scopes change no op, and a TPU trace carries them as
each op's `tf_op` stat (DESIGN.md §Tracing).
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import Literal, Tuple

import jax
import jax.numpy as jnp

from repro.core import qr as qr_mod
from repro.core import sketch as sketch_mod
from repro.core.eigh_jacobi import svd_via_gram

SmallSVD = Literal["lapack", "gram", "gram_jacobi"]


@dataclass(frozen=True)
class RSVDConfig:
    """Algorithm configuration. Defaults = paper-faithful Algorithm 1.

    Note on step 2: the paper says "Compute q steps of *QR iteration*
    Y = (A A^H)^q A Omega" — i.e. power iteration with QR re-orthonormalization
    between applications (what cuSOLVER gesvdr implements), NOT a raw GEMM
    chain.  The raw chain is available as power_scheme='plain' for ablation;
    it demonstrably loses the tail singular values to round-off (the sigma^(2q+1)
    underflow documented in EXPERIMENTS.md).

    Execution-shape switches (DESIGN.md §"Blocked & batched execution"):
      * block_rows  — stream the tall dimension in row panels of this height
                      through the blocked range finder (core/blocked.py): A
                      itself (host numpy accepted) is device-resident one
                      block_rows x n panel at a time, and only sketch-width
                      (m x s) state stays on device — an n/s reduction vs.
                      holding A, see core/blocked.py for the exact contract.
      * block_cols  — optional inner column-panel width for the streamed
                      sketch accumulation Y += A_panel @ Omega_panel (panel-
                      offset counter RNG; Omega never materialized whole).
      * batched     — declare the workload a fleet of small SVDs: the input
                      MUST be 3-D [B, m, n] (ValueError otherwise) and runs
                      under one vmap (per-channel PCA, per-layer GaLore /
                      PowerSGD compression).  3-D inputs take the batched
                      path automatically even without the flag; setting it
                      makes an accidental 2-D input fail loudly instead of
                      silently running one big dense SVD."""

    oversample: int = 10          # s = k + oversample   (paper: s = O(k/eps))
    power_iters: int = 2          # q in Algorithm 1 step 2
    power_scheme: str = "stabilized"  # paper: "q steps of QR iteration"
    qr_method: qr_mod.QRMethod = "householder"
    small_svd: SmallSVD = "lapack"
    sketch_kind: sketch_mod.SketchKind = "gaussian"
    fused_sketch: bool = False    # Pallas fused RNG+GEMM (TPU fast path)
    fused_power: bool = False     # one-pass Aᵀ(A·X) power step (EXPERIMENTS.md)
    kernel_backend: str = "jnp"   # "pallas" routes CQR Gram+TRSM through kernels
    block_rows: int | None = None  # panel-stream the tall dimension
    block_cols: int | None = None  # panel-stream the sketch reduction
    batched: bool = False          # vmap over a leading batch dimension
    pipeline_depth: int | None = None  # streamed-panel prefetch depth (None =
    #                               auto: double-buffered for host sources,
    #                               1 — fully synchronous — otherwise; the
    #                               planner stamps the effective value on
    #                               every streamed/adaptive ExecutionPlan)

    @staticmethod
    def faithful() -> "RSVDConfig":
        return RSVDConfig()

    @staticmethod
    def fast() -> "RSVDConfig":
        """The TPU-optimized configuration (beyond-paper): CholeskyQR2 with
        Pallas-backed Gram + TRSM, the in-VMEM RNG sketch fused with its
        first Gram, and the one-pass-per-iteration fused power step."""
        return RSVDConfig(
            power_scheme="stabilized",
            qr_method="cqr2",
            small_svd="gram_jacobi",
            fused_sketch=True,
            fused_power=True,
            kernel_backend="pallas",
        )

    @staticmethod
    def streaming(block_rows: int = 4096) -> "RSVDConfig":
        """Out-of-core configuration: CholeskyQR2 accumulation over row
        panels (Householder QR of a panel-split Y is not expressible as a
        panel-local op; the Gram trick is — see core/blocked.py), with the
        panel prefetch DOUBLE-BUFFERED — panel i+1's host->device copy
        overlaps panel i's compute (linalg/pipeline.py; the planner still
        clamps the depth to what the HBM budget and panel count allow)."""
        return RSVDConfig(
            power_scheme="stabilized",
            qr_method="cqr2",
            small_svd="lapack",
            block_rows=block_rows,
            pipeline_depth=2,
        )


def _small_svd(B: jax.Array, method: SmallSVD):
    """Step 5 on every path, under the `rsvd.small_svd` scope."""
    with jax.named_scope("rsvd.small_svd"):
        if method == "lapack":
            return jnp.linalg.svd(B, full_matrices=False)  # repro: noqa[RL006]: B is sketch-width (s x n), Algorithm 1 step 5
        if method == "gram":
            return svd_via_gram(B, use_jacobi=False)
        if method == "gram_jacobi":
            return svd_via_gram(B, use_jacobi=True)
    raise ValueError(f"unknown small_svd: {method}")


def _sketch(A: jax.Array, s: int, seed, cfg: RSVDConfig) -> jax.Array:
    if cfg.sketch_kind in sketch_mod.STRUCTURED_KINDS:
        # SRHT / CountSketch apply by transform (sign flip + FWHT + column
        # subsample / signed segment-sum) — O(mn log n) / O(mn) instead of
        # the O(mns) GEMM, and nothing to fuse: there is no RNG tile.
        return sketch_mod.apply_structured(A, s, seed, cfg.sketch_kind)
    if cfg.fused_sketch and A.dtype != jnp.float64:
        # Fused RNG+GEMM Pallas kernel — Omega never materialized in HBM.
        # The seed is a traced SMEM scalar: seed sweeps / GaLore refreshes /
        # the batched vmap path all reuse one compiled program.
        from repro.kernels.ops import sketch_matmul

        return sketch_matmul(A, s, seed, kind=cfg.sketch_kind)
    omega = sketch_mod.sketch_matrix(A.shape[1], s, seed, cfg.sketch_kind, dtype=A.dtype)
    return A @ omega


def _use_fused_power(
    A: jax.Array, cfg: RSVDConfig, s: int, vmem_budget: int | None = None
) -> bool:
    """The one-pass power path needs fp32-accumulating kernels (not the f64
    faithful setting), a CholeskyQR-family range finder (the Y-side
    re-orthonormalization is expressed through Gram + TRSM), and a working
    set — the A strip plus the n x s accumulators — that fits the VMEM
    limit the kernels compile with, `hw.VMEM_BYTES` (interpret mode has no
    limit, but the config path must not select a kernel that cannot
    compile on hardware; beyond the budget the blocked/streaming and
    distributed paths are the intended scale-out).  The execution planner
    (repro/linalg/planner.py) evaluates the same gate at plan time,
    parameterized by its Budget — `vmem_budget` keeps the two in lockstep."""
    from repro.kernels.ops import _block, _select_blocks
    from repro.kernels.power_step import fused_power_vmem_bytes
    from repro.roofline import hw

    if vmem_budget is None:
        vmem_budget = hw.VMEM_BYTES
    m, n = A.shape
    # Model the kernel's ACTUAL footprint: the bm the wrapper will select
    # (autotune cache included) and the padded dims it will allocate.
    bm = _select_blocks("power_step", (m, n, s), A.dtype)[0]
    n_pad = n + (-n) % _block(n)
    s_pad = s + (-s) % _block(s)
    # cqr3 (shifted, for kappa up to ~1/eps) and single-pass cqr are
    # deliberately excluded: the fused body hardwires CQR2-style
    # re-orthonormalization, and a caller asking for a different variant
    # should get exactly that, unfused.
    return (
        cfg.fused_power
        and A.dtype != jnp.float64
        and (cfg.power_scheme == "plain" or cfg.qr_method == "cqr2")
        and fused_power_vmem_bytes(n_pad, s_pad, bm=bm,
                                   dtype_bytes=A.dtype.itemsize) <= vmem_budget
    )


def _cqr2_factor(Y: jax.Array, G1: jax.Array | None):
    """CholeskyQR2 of Y reusing an already-accumulated first Gram.

    Returns (Q1, R2, R_tot): Q1 is the first-pass basis, Q = Q1 R2⁻¹ is
    materialized lazily by callers that actually need it, and R_tot = R2 R1
    satisfies Y ≈ Q R_tot.  G1 comes for free from the fused kernels'
    Gram epilogue (sketch_gram / power_step), killing CQR's first pass
    over Y; when None it is computed through the active kernel backend.
    """
    if G1 is None:
        G1 = qr_mod.gram(Y)
    R1 = qr_mod.cholesky_r_from_gram(G1.astype(Y.dtype))
    Q1 = qr_mod.tri_solve_right(Y, R1)
    G2 = qr_mod.gram(Q1).astype(Y.dtype)
    qr_mod.record_ortho_gram(G2)  # first-pass health probe, free byproduct
    R2 = qr_mod.cholesky_r_from_gram(G2)
    return Q1, R2, R2 @ R1


def _rsvd_body_fused(
    A: jax.Array, k: int, cfg: RSVDConfig, seed
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Algorithm 1 with the one-pass range finder (kernels/power_step.py).

    Each stabilized power iteration does exactly ONE read of A: the fused
    kernel returns Y = A·Qz, W = AᵀY, and G = YᵀY together, and CholeskyQR
    turns W into the next projection without touching A again —
    Q = Y R⁻¹  ⇒  AᵀQ = W R⁻¹  (a sketch-width TRSM), and the final
    projection B = QᵀA = (W R⁻¹)ᵀ falls out of the last W.  The sketch pass
    itself emits W (sketch_power), so reads of A total 1 + q, vs 2q + 2
    unfused (two per iteration plus the sketch and the final projection).
    """
    from repro.kernels import ops

    m, n = A.shape
    s = min(k + cfg.oversample, min(m, n))

    if cfg.power_scheme == "plain":
        # Ablation path: Y = A (AᵀA)^q Ω as a chain of fused steps (each one
        # read of A), materialized Omega (the plain scheme is the paper's
        # raw-GEMM ablation, not the production path).
        omega = sketch_mod.sketch_matrix(n, s, seed, cfg.sketch_kind, dtype=A.dtype)
        X = omega
        for _ in range(cfg.power_iters):
            _, X = ops.power_step(A, X)
        Y = A @ X
        Q = qr_mod.orthonormalize(Y, cfg.qr_method)
        B = Q.T @ A
        U_b, S, Vt = _small_svd(B, cfg.small_svd)
        U = Q @ U_b
        return U[:, :k], S[:k], Vt[:k, :]

    # Stabilized scheme, CholeskyQR-family orthonormalization on the Y side.
    # The sketch pass already emits W = AᵀY (sketch_power strip layout), so
    # even the FIRST power iteration closes through a sketch-width TRSM
    # instead of re-reading A: reads of A = 1 + q exactly.
    with jax.named_scope("rsvd.sketch"):
        if cfg.fused_sketch and cfg.sketch_kind not in sketch_mod.STRUCTURED_KINDS:
            Y, W, G1 = ops.sketch_power(A, s, seed, kind=cfg.sketch_kind)
        else:
            # structured kinds have no in-kernel RNG — materialize Omega and
            # still take the one-pass strip kernel for Y / W / G
            omega = sketch_mod.sketch_matrix(n, s, seed, cfg.sketch_kind, dtype=A.dtype)
            Y, W, G1 = ops.power_step(A, omega, with_gram=True)
    for _ in range(cfg.power_iters):
        with jax.named_scope("rsvd.orth"):
            Q1, R2, R_tot = _cqr2_factor(Y, G1)
            Z = qr_mod.tri_solve_right(W, R_tot)           # AᵀQ without reading A
            Qz = qr_mod.orthonormalize(Z, cfg.qr_method)   # n x s, sketch-width
        with jax.named_scope("rsvd.power"):
            Y, W, G1 = ops.power_step(A, Qz, with_gram=True)
    with jax.named_scope("rsvd.orth"):
        Q1, R2, R_tot = _cqr2_factor(Y, G1)
        Q = qr_mod.tri_solve_right(Q1, R2)                 # step 3 basis
    with jax.named_scope("rsvd.project"):
        B = qr_mod.tri_solve_right(W, R_tot).T             # step 4 without reading A
    U_b, S, Vt = _small_svd(B, cfg.small_svd)              # step 5
    with jax.named_scope("rsvd.assemble"):
        U = Q @ U_b                                        # step 6
        return U[:, :k], S[:k], Vt[:k, :]


def _rsvd_body(
    A: jax.Array, k: int, cfg: RSVDConfig, seed
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Algorithm 1 steps 1-6 with the range finder on the given orientation.

    ``seed`` is always traced (the counter RNG takes it as data, in jnp and
    in the Pallas kernels alike).
    """
    m, n = A.shape
    s = min(k + cfg.oversample, min(m, n))
    if _use_fused_power(A, cfg, s):
        return _rsvd_body_fused(A, k, cfg, seed)
    with jax.named_scope("rsvd.sketch"):
        Y = _sketch(A, s, seed, cfg)                   # step 1-2a: A @ Omega
    if cfg.power_iters > 0:
        if cfg.power_scheme == "plain":
            with jax.named_scope("rsvd.power"):
                for _ in range(cfg.power_iters):       # step 2: (A A^T)^q
                    Y = A @ (A.T @ Y)
        else:
            Y = _stabilized_power(A, Y, cfg)
    with jax.named_scope("rsvd.orth"):
        Q = qr_mod.orthonormalize(Y, cfg.qr_method)    # step 3
    with jax.named_scope("rsvd.project"):
        B = Q.T @ A                                    # step 4
    U_b, S, Vt = _small_svd(B, cfg.small_svd)          # step 5
    with jax.named_scope("rsvd.assemble"):
        U = Q @ U_b                                    # step 6
        return U[:, :k], S[:k], Vt[:k, :]


@functools.partial(jax.jit, static_argnames=("k", "cfg"))
def _randomized_svd_dense(
    A: jax.Array, seed: jax.Array, k: int, cfg: RSVDConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-device in-memory path.  The seed is TRACED: changing it
    (GaLore refreshes, per-slice loops, seed sweeps) reuses the compiled
    program — the counter RNG takes the seed as data, including inside the
    fused Pallas sketch (an SMEM scalar operand)."""
    with qr_mod.kernel_backend(cfg.kernel_backend):
        m, n = A.shape
        if m < n:
            V, S, Ut = _rsvd_body(A.T, k, cfg, seed)
            return Ut.T, S, V.T
        return _rsvd_body(A, k, cfg, seed)


@functools.partial(jax.jit, static_argnames=("k", "cfg", "fault_key"))
def _randomized_svd_dense_probed(
    A: jax.Array, seed: jax.Array, k: int, cfg: RSVDConfig, fault_key=()
) -> Tuple[Tuple[jax.Array, jax.Array, jax.Array], dict]:
    """Guarded compiled twin of `_randomized_svd_dense`: traces the SAME
    body under an open guard probe sink and returns (factors, probes) —
    the probes (breakdown / ortho / cond scalars, see linalg/guard.py) are
    extra jit outputs the driver folds back into its own sink.

    `fault_key` (= linalg.faults.fingerprint(), static) keys the compile
    cache on the active fault set so a fault-injected trace can never
    shadow a clean entry.  The unprobed twin keeps its own cache untouched,
    so guard `off` stays bit-identical and re-trace-free."""
    del fault_key
    from repro.linalg import guard as guard_mod

    with qr_mod.kernel_backend(cfg.kernel_backend), guard_mod.collecting() as sink:
        m, n = A.shape
        if m < n:
            V, S, Ut = _rsvd_body(A.T, k, cfg, seed)
            out = (Ut.T, S, V.T)
        else:
            out = _rsvd_body(A, k, cfg, seed)
    return out, sink.traced()


def _as_plannable(A):
    """Wrap a raw array the way the historical dispatch understood it:
    3-D -> StackedOp; 2-D -> DenseOp even for host numpy (the old entry
    point moved host arrays to device wholesale unless cfg.block_rows
    streamed them, and the planner's `overrides` dispatch keys on
    cfg.block_rows/batched, not on residency)."""
    from repro.linalg.operators import DenseOp, StackedOp

    if getattr(A, "ndim", 2) == 3:
        return StackedOp(A)
    return DenseOp(A)


def randomized_svd(
    A: jax.Array,
    k: int,
    cfg: RSVDConfig = RSVDConfig(),
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """DEPRECATED shim over `repro.linalg.svd` — kept so pre-facade callers
    keep working unchanged.  The planner reproduces this entry point's
    historical dispatch exactly (3-D -> batched, cfg.block_rows ->
    streamed, else dense), so fixed-seed results are bit-identical.
    """
    warnings.warn(
        "randomized_svd is deprecated; use repro.linalg.svd (operator sources"
        " + execution plans — see DESIGN.md §'API: operators and plans')",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import linalg

    return linalg.svd(_as_plannable(A), k, overrides=cfg, seed=seed)


def _stabilized_power(A: jax.Array, Y: jax.Array, cfg: RSVDConfig) -> jax.Array:
    for _ in range(cfg.power_iters):
        with jax.named_scope("rsvd.orth"):
            Q = qr_mod.orthonormalize(Y, cfg.qr_method)
        with jax.named_scope("rsvd.power"):
            Z = A.T @ Q
        with jax.named_scope("rsvd.orth"):
            Qz = qr_mod.orthonormalize(Z, cfg.qr_method)
        with jax.named_scope("rsvd.power"):
            Y = A @ Qz
    return Y


def randomized_eigvals(
    A: jax.Array, k: int, cfg: RSVDConfig = RSVDConfig(), seed: int = 0
) -> jax.Array:
    """DEPRECATED shim over `repro.linalg.eigvals` (paper's eigenvalue-
    benchmark mode: steps 1-5 of Algorithm 1, discarding U and V)."""
    warnings.warn(
        "randomized_eigvals is deprecated; use repro.linalg.eigvals (operator"
        " sources + execution plans — see DESIGN.md §'API: operators and plans')",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import linalg

    return linalg.eigvals(_as_plannable(A), k, overrides=cfg, seed=seed)


@functools.partial(jax.jit, static_argnames=("k", "cfg"))
def _randomized_eigvals_dense(
    A: jax.Array, seed, k: int, cfg: RSVDConfig = RSVDConfig()
) -> jax.Array:
    m, n = A.shape
    if m < n:
        return _randomized_eigvals_dense(A.T, seed, k, cfg)
    with qr_mod.kernel_backend(cfg.kernel_backend):
        s = min(k + cfg.oversample, min(m, n))
        Y = _sketch(A, s, seed, cfg)
        if cfg.power_iters > 0:
            if cfg.power_scheme == "plain":
                for _ in range(cfg.power_iters):
                    Y = A @ (A.T @ Y)
            else:
                Y = _stabilized_power(A, Y, cfg)
        Q = qr_mod.orthonormalize(Y, cfg.qr_method)
        B = Q.T @ A
        if cfg.small_svd == "lapack":
            S = jnp.linalg.svd(B, compute_uv=False)  # repro: noqa[RL006]: B is sketch-width (s x n), sigma-only finisher
        else:
            G = B @ B.T
            if cfg.small_svd == "gram_jacobi":
                from repro.core.eigh_jacobi import jacobi_eigh

                w, _ = jacobi_eigh(G)
            else:
                w = jnp.linalg.eigvalsh(G)[::-1]
            S = jnp.sqrt(jnp.maximum(w, 0.0))
    return S[:k]


def low_rank_error(A: jax.Array, U: jax.Array, S: jax.Array, Vt: jax.Array) -> jax.Array:
    """Relative Frobenius error ||A - U S Vt||_F / ||A||_F (paper's metric).

    Materializes the full m x n reconstruction — fine for in-core arrays.
    Streamed/host/composed sources should use `repro.linalg.residual`, the
    panel-wise version that never forms an m x n temporary."""
    R = A - (U * S[None, :]) @ Vt
    return jnp.sqrt(jnp.sum(R * R) / jnp.sum(A * A))


def truncation_error(S_full: jax.Array, k: int) -> jax.Array:
    """||A - A_k||_F / ||A||_F from the exact spectrum (the 1+eps reference)."""
    tail = jnp.sum(S_full[k:] ** 2)
    return jnp.sqrt(tail / jnp.sum(S_full**2))
