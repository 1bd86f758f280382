"""`repro.linalg` public facade: one call-site pattern for every rSVD path.

    U, S, Vt = linalg.svd(source, k)                 # planner picks the path
    pl       = linalg.plan(source, k)                # inspect before running
    U, S, Vt = linalg.svd(source, k, plan=pl)        # execute a pinned plan
    err      = linalg.residual(source, (U, S, Vt))   # panel-wise, no m x n temp

Spec-driven decompositions (PR 4): call sites that know an ACCURACY rather
than a rank state it, and pick a factorization kind from the registry:

    dec = linalg.decompose(source, linalg.Tolerance(1e-2))        # adaptive rank
    dec = linalg.decompose(source, linalg.Energy(0.95), kind="pca")
    Q, B = linalg.decompose(source, linalg.Rank(64), kind="qb")
    w, V = linalg.decompose(psd, linalg.Tolerance(1e-3), kind="eigh")

`source` is anything `as_linop` accepts: a device array (DenseOp), a host
numpy array (HostOp, panel-streamed), a 3-D stack (StackedOp), a
`ShardedOp(A, mesh, axis)`, or a composed operator (CenteredOp, ScaledOp,
LowRankUpdateOp) — the last class runs the generic operator body, nothing
materialized.  Execution delegates to the SAME numerics as the historical
entry points (`core/rsvd.py`, `core/blocked.py`, `core/distributed.py`), so
fixed-seed results are bit-identical to the pre-facade paths; `svd`,
`eigvals`, and `pca` survive as thin Rank-spec wrappers.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qr as qr_mod
from repro.core import sketch as sketch_mod
from repro.core.rsvd import RSVDConfig
from repro.linalg import faults as faults_mod
from repro.linalg import guard as guard_mod
from repro.linalg import pipeline as pipeline_mod
from repro.linalg import planner as planner_mod
from repro.linalg import registry as registry_mod
from repro.linalg import snapshot as snapshot_mod
from repro.linalg import trace
from repro.linalg.operators import LinOp, ShardedOp, as_linop, prefetch_panels
from repro.linalg.planner import Budget, ExecutionPlan
from repro.linalg.spec import Rank, Spec, as_spec

SVDResult = Tuple[jax.Array, jax.Array, jax.Array]


def f32_matmuls(fn):
    """Run `fn` with f32 matmuls at full f32 precision, unless the caller
    has set `jax_default_matmul_precision` itself.

    A TPU multiplies f32 matrices in one bf16 pass by default (about three
    significant digits), which caps a factorization's accuracy near 1e-3
    whatever its rank; the accuracy contracts here (Rank residuals,
    Tolerance and Energy targets) assume f32 arithmetic.  jax reads the
    setting while it traces, so it reaches the Pallas kernels' dots too,
    and it is part of the jit cache key.  The CPU computes f32 matmuls in
    f32 either way."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if jax.config.jax_default_matmul_precision is not None:
            return fn(*args, **kwargs)
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapper


def plan(op, spec, budget: Optional[Budget] = None,
         overrides: Optional[RSVDConfig] = None, kind: str = "svd",
         nnz: Optional[int] = None, guard=None,
         validate: bool = False) -> ExecutionPlan:
    """See planner.plan — re-exported as part of the facade.

    Mirrors `decompose`'s source preparation (e.g. kind="pca" wraps in
    CenteredOp) so a plan built here describes the operator that will
    actually execute when pinned via `decompose(..., plan=pl)`.  `guard`
    ("off" | "report" | "retry" or a GuardPolicy) and `validate` set the
    guarded-execution fields — linalg/guard.py."""
    entry = registry_mod.get(kind)
    op = as_linop(op)
    if entry.prepare is not None:
        op = entry.prepare(op)
    return planner_mod.plan(op, spec, budget=budget, overrides=overrides,
                            kind=kind, nnz=nnz, guard=guard,
                            validate=validate)


# ---------------------------------------------------------------------------
# Spec-driven decompositions: the registry front door
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Decomposition:
    """What `decompose` returns: the factors plus the full decision/record.

    `factors` is kind-shaped — (U, S, Vt) for svd, (Q, B) for qb, (w, V)
    for eigh, (perm_rows, L, U, perm_cols) for lu, PCAResult field order
    for pca — and the object unpacks like that tuple.  `plan` carries the
    PLANNED rank schedule; `rank_history` is the prefix that actually ran
    (adaptive solves stop early), and `err_history` the posterior relative-
    error estimate after each growth panel.  `health` is the guard's
    HealthReport when the plan's GuardPolicy is "report" or "retry"
    (linalg/guard.py) and None under guard "off"."""

    kind: str
    spec: Spec
    plan: ExecutionPlan
    rank: int
    factors: tuple
    rank_history: Tuple[int, ...]
    err_history: Tuple[float, ...]
    health: Optional[guard_mod.HealthReport] = None

    def __iter__(self):
        return iter(self.factors)

    def __getitem__(self, i):
        return self.factors[i]

    def __len__(self):
        return len(self.factors)


@f32_matmuls
def decompose(
    a,
    spec,
    kind: str = "svd",
    *,
    plan: Optional[ExecutionPlan] = None,
    overrides: Optional[RSVDConfig] = None,
    budget: Optional[Budget] = None,
    seed: int = 0,
    guard=None,
    validate: Optional[bool] = None,
    checkpoint=None,
) -> Decomposition:
    """Factorize `a` to the accuracy `spec` with the registry entry `kind`.

    `spec` is a rank (int / `Rank`) or an adaptive accuracy contract
    (`Tolerance`, `Energy`); `kind` is one of `registry.kinds()` —
    "svd" | "eigh" | "qb" | "lu" | "pca".  Rank-spec svd is bit-identical
    to `linalg.svd(a, k)` at fixed seed (same plan, same executors).

    `guard` / `validate` (linalg/guard.py): explicit arguments win over a
    pinned plan's fields; None inherits them.  Under guard "report" /
    "retry" the result's `health` carries the probe verdict (and the
    ladder trail for retry); `validate=True` screens non-finite input
    before factors can silently go NaN.

    `checkpoint` (linalg/snapshot.py) makes a streamed/adaptive solve
    resumable: a directory path (or `Checkpointer` / `RunControl`) where
    engine state is persisted at panel-group boundaries.  An interrupted
    call re-issued with the same arguments and checkpoint directory
    resumes from the last snapshot, bit-identical to an uninterrupted run;
    `None` (default) adds zero work and zero HBM traffic.

    The call runs under a `linalg.decompose` span whose ``solve`` is the
    `linalg.solves` count, the identifier its child spans share
    (linalg/trace.py)."""
    solve = trace.add("linalg.solves")
    with trace.span("linalg.decompose", solve=solve):
        spec = as_spec(spec)
        entry = registry_mod.get(kind)
        op = as_linop(a)
        if entry.prepare is not None:
            op = entry.prepare(op)
        if plan is not None and (plan.kind != kind or plan.spec != spec):
            raise ValueError(
                f"pinned plan was built for kind={plan.kind!r} "
                f"spec={plan.spec.describe() if plan.spec else None}, which does "
                f"not match the requested kind={kind!r} spec={spec.describe()} — "
                "re-plan with linalg.plan(a, spec, kind=kind)"
            )
        pl = plan if plan is not None else registry_mod.cached_plan(
            op, spec, budget=budget, overrides=overrides, kind=kind,
            guard=guard, validate=bool(validate),
        )
        pl = _with_guard_overrides(pl, guard, validate, pinned=plan is not None)

        def execute(op_, pl_, seed_):
            with trace.span("linalg.execute"):
                return entry.execute(op_, spec, pl_, seed_)

        with snapshot_mod.maybe_scope(checkpoint), \
                guard_mod.validated(op, pl.validate):
            if pl.guard.mode != "off":
                ortho = None
                if entry.ortho_factor is not None:
                    ortho = lambda res: entry.ortho_factor(res[0])  # noqa: E731
                result, health = guard_mod.run_guarded(
                    execute, op, pl, seed, ortho_factor=ortho)
                factors, rank, rank_history, err_history = result
            else:
                health = None
                factors, rank, rank_history, err_history = execute(op, pl, seed)
        return Decomposition(
            kind=kind,
            spec=spec,
            plan=pl,
            rank=int(rank),
            factors=tuple(factors),
            rank_history=tuple(rank_history),
            err_history=tuple(err_history),
            health=health,
        )


def _with_guard_overrides(pl: ExecutionPlan, guard, validate,
                          pinned: bool) -> ExecutionPlan:
    """Apply explicit guard/validate arguments over a plan's fields.

    Only meaningful for PINNED plans (a fresh plan was already built with
    them); neither field changes a healthy solve's numerics, so replacing
    them on a pinned plan cannot invalidate its execution decisions."""
    if not pinned:
        return pl
    import dataclasses

    updates = {}
    if guard is not None:
        updates["guard"] = guard_mod.as_guard(guard)
    if validate is not None:
        updates["validate"] = bool(validate)
    return dataclasses.replace(pl, **updates) if updates else pl


def _dense_array(op: LinOp) -> jax.Array:
    """The device array a dense plan executes on (host numpy under a dense
    plan moves wholesale, matching the historical entry point)."""
    return op.array if isinstance(op.array, jax.Array) else jnp.asarray(op.array)


@f32_matmuls
def svd(
    a,
    k: int,
    *,
    plan: Optional[ExecutionPlan] = None,
    overrides: Optional[RSVDConfig] = None,
    budget: Optional[Budget] = None,
    seed: int = 0,
    guard=None,
    validate: Optional[bool] = None,
) -> SVDResult:
    """Rank-k randomized SVD of any operator source.  Returns (U, S, Vt)
    with U: m x k, S: k, Vt: k x n (leading batch axis for StackedOp).

    This is the `Rank(k)`-spec thin wrapper: `decompose(a, Rank(k))` runs
    the SAME plan and executors, bit-identical at fixed seed.

    Guarded execution: `guard="retry"` (or a guard-carrying plan) recovers
    breakdowns through the escalation ladder but this wrapper returns the
    bare factor tuple — use `decompose(a, k, guard=...)` when you want the
    HealthReport itself."""
    k = _fixed_rank(k, "svd")
    op = as_linop(a)
    pl = plan if plan is not None else registry_mod.cached_plan(
        op, k, budget=budget, overrides=overrides, guard=guard,
        validate=bool(validate))
    pl = _with_guard_overrides(pl, guard, validate, pinned=plan is not None)
    with guard_mod.validated(op, pl.validate):
        if pl.guard.mode != "off":
            result, _health = guard_mod.run_guarded(
                lambda op_, pl_, seed_: _execute_svd_plan(op_, k, pl_, seed_),
                op, pl, seed,
                ortho_factor=lambda res: None if getattr(res[0], "ndim", 2) == 3 else res[0],
            )
            return result
        return _execute_svd_plan(op, k, pl, seed)


def _fixed_rank(k, entry: str) -> int:
    """The svd/eigvals wrappers are fixed-rank only: adaptive specs must go
    through `decompose` (which returns the selected rank and trajectory)."""
    spec = as_spec(k)
    if not isinstance(spec, Rank):
        raise ValueError(
            f"linalg.{entry} takes a rank; for adaptive specs like "
            f"{spec.describe()} use linalg.decompose(a, spec)"
        )
    return spec.k


def _execute_svd_plan(op: LinOp, k: int, pl: ExecutionPlan, seed) -> SVDResult:
    """Execute a fixed-rank plan through the historical per-path numerics
    (shared by `svd` and the registry's Rank-spec handler)."""
    if pl.path == "adaptive":
        raise ValueError(
            "an adaptive plan cannot execute through the fixed-rank svd "
            "wrapper; pass it to linalg.decompose(a, spec, plan=pl)"
        )
    cfg = pl.to_config()
    if pl.path == "dense":
        from repro.core import rsvd as rsvd_mod

        A = _dense_array(op)
        seed_arr = jnp.asarray(seed, jnp.uint32)
        with trace.span("engine.dense"):
            if guard_mod.active_sink() is not None:
                # guarded run: the probed compiled twin returns the health
                # scalars as extra jit outputs (the unguarded program and its
                # cache entry are untouched — guard "off" stays bit-identical)
                out, probes = rsvd_mod._randomized_svd_dense_probed(
                    A, seed_arr, k, cfg, faults_mod.fingerprint())
                guard_mod.absorb(probes)
                return out
            return rsvd_mod._randomized_svd_dense(A, seed_arr, k, cfg)
    if pl.path == "streamed":
        from repro.core import blocked

        return blocked.svd_streamed(op.array, k, cfg, seed=seed)
    if pl.path == "batched":
        from repro.core import blocked

        return blocked.svd_batched(op.array, k, cfg, seed=seed)
    if pl.path == "sharded":
        from repro.core import distributed

        mesh, axis = op.sharding
        return distributed.svd_sharded(op.array, k, mesh, axis, cfg, seed=seed)
    if pl.path == "matfree":
        # host-rooted composed sources stream underneath matmat/rmatmat;
        # the ambient scope hands them the plan's prefetch depth
        with pipeline_mod.default_depth(pl.pipeline_depth):
            return _matfree_svd(op, k, pl, seed)
    if pl.path == "sparse":
        # the sparse path is the operator body with SpMM products; when the
        # plan claims a fused sketch, _matfree_svd routes through the
        # source's `sketch` hook (SparseOp -> the Pallas SpMM kernel)
        return _matfree_svd(op, k, pl, seed)
    raise ValueError(f"unknown execution path: {pl.path}")


@f32_matmuls
def eigvals(
    a,
    k: int,
    *,
    plan: Optional[ExecutionPlan] = None,
    overrides: Optional[RSVDConfig] = None,
    budget: Optional[Budget] = None,
    seed: int = 0,
) -> jax.Array:
    """k largest singular values only (the paper's eigenvalue-benchmark
    mode: Algorithm 1 steps 1-5, Sigma only)."""
    k = _fixed_rank(k, "eigvals")
    op = as_linop(a)
    pl = plan if plan is not None else registry_mod.cached_plan(
        op, k, budget=budget, overrides=overrides)
    cfg = pl.to_config()
    if pl.path == "dense":
        from repro.core import rsvd as rsvd_mod

        return rsvd_mod._randomized_eigvals_dense(
            _dense_array(op), jnp.asarray(seed, jnp.uint32), k, cfg
        )
    if pl.path == "streamed":
        from repro.core import blocked

        return blocked.eigvals_streamed(op.array, k, cfg, seed=seed)
    if pl.path in ("matfree", "sparse"):
        with pipeline_mod.default_depth(pl.pipeline_depth):
            return _matfree_svd(op, k, pl, seed, want_uv=False)
    # batched / sharded: Sigma rides the factor solve
    return svd(op, k, plan=pl, seed=seed)[1]


# ---------------------------------------------------------------------------
# Matrix-free body: Algorithm 1 over the LinOp protocol (composed operators)
# ---------------------------------------------------------------------------

def _matfree_svd(op: LinOp, k: int, pl: ExecutionPlan, seed, want_uv: bool = True):
    """Algorithm 1 phrased purely through matmat/rmatmat — serves any
    composed operator (centered, scaled, deflated) without materializing it.
    The range finder works on the taller orientation, like the dense path.
    ``want_uv=False`` is the Sigma-only mode: steps 1-5, skipping the
    step-6 U assembly (the m x s GEMM).

    NOTE: the stabilized loop below deliberately mirrors the unfused body
    in core/rsvd.py (`_stabilized_power` / `_rsvd_body`) with A@ / Aᵀ@
    replaced by the operator products — numerics fixes there must land
    here too (tests/test_planner.py pins the paths against each other
    through the CenteredOp == pca_exact property)."""
    m_raw, n_raw = op.shape
    if m_raw < n_raw:
        if not want_uv:
            return _matfree_svd(op.T, k, pl, seed, want_uv=False)
        V, S, Ut = _matfree_svd(op.T, k, pl, seed)
        return Ut.T, S, V.T
    with qr_mod.kernel_backend(pl.kernel_backend):
        m, n = op.shape
        s = min(k + pl.oversample, min(m, n))
        fdtype = jnp.promote_types(op.dtype, jnp.float32)
        sketcher = getattr(op, "sketch", None)
        if pl.fused_sketch and sketcher is not None:
            # source-fused sketch (SparseOp: block-ELL SpMM with Omega tiles
            # generated in VMEM — Omega never exists in HBM)
            Y = sketcher(s, jnp.asarray(seed, jnp.uint32), pl.sketch_kind).astype(fdtype)
        else:
            omega = sketch_mod.sketch_matrix(
                n, s, jnp.asarray(seed, jnp.uint32), pl.sketch_kind, dtype=fdtype
            )
            Y = op.matmat(omega)
        for _ in range(pl.power_iters):
            if pl.power_scheme == "plain":
                Y = op.matmat(op.rmatmat(Y))
            else:
                Q = qr_mod.orthonormalize(Y, pl.qr_method)
                Z = op.rmatmat(Q)
                Qz = qr_mod.orthonormalize(Z, pl.qr_method)
                Y = op.matmat(Qz)
        Q = qr_mod.orthonormalize(Y, pl.qr_method)
        B = op.rmatmat(Q).T                      # (s, n) without forming A
        from repro.core.rsvd import _small_svd

        U_b, S, Vt = _small_svd(B, pl.small_svd)
        if not want_uv:
            return S[:k]
        U = Q @ U_b
        return U[:, :k], S[:k], Vt[:k, :]


# ---------------------------------------------------------------------------
# PCA on the centered OPERATOR (the m x n centered temporary is gone)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "pl"))
def _pca_centered_dense(X: jax.Array, seed: jax.Array, k: int, pl: ExecutionPlan):
    """Jitted PCA over the centered OPERATOR of a device-resident X: the
    whole pipeline (mean, sketch, power loop, small SVD) is one compiled
    program per (shape, plan) — the repeated-PCA hot path — while X - mu
    still never materializes (the CenteredOp matmat/rmatmat carry the
    correction).  ExecutionPlan is frozen/hashable, so it keys the cache;
    the seed is traced."""
    from repro.linalg.operators import CenteredOp, DenseOp

    mu = jnp.mean(X, axis=0)
    _, S, Vt = _matfree_svd(CenteredOp(DenseOp(X), mu), k, pl, seed)
    return mu, S, Vt


@f32_matmuls
def pca(x, k, *, overrides: Optional[RSVDConfig] = None,
        budget: Optional[Budget] = None, seed: int = 0):
    """Top-k principal components of X (N x d) via the CenteredOp source.

    `k` is a rank (int) or an accuracy spec: `Energy(p)` keeps the smallest
    rank explaining fraction p of the variance, `Tolerance(eps)` targets a
    relative reconstruction error (both run the adaptive QB engine over
    the centered operator — the spec-driven path of the registry).

    Returns a `repro.core.pca.PCAResult`.  Unlike the historical
    `core.pca.pca`, the centered matrix X - mu is never materialized: the
    range finder consumes `CenteredOp(X)` through matmat/rmatmat.  Device-
    resident X runs as one jitted program (cached per shape/plan); host
    numpy sources stream row panels eagerly."""
    from repro.core.pca import PCAResult
    from repro.linalg.operators import CenteredOp, DenseOp

    spec = as_spec(k)
    if not isinstance(spec, Rank):
        dec = decompose(x, spec, kind="pca", overrides=overrides,
                        budget=budget, seed=seed)
        components, expvar, svals, mu = dec.factors
        return PCAResult(components=components, explained_variance=expvar,
                         singular_values=svals, mean=mu)
    k = spec.k
    op = as_linop(x)
    n = op.shape[0]
    if type(op) is DenseOp:  # HostOp subclasses DenseOp — excluded by type()
        # Plan on shapes only (a dummy mu skips the eager column_means),
        # then run the compiled pipeline.
        shape_op = CenteredOp(op, mu=jnp.zeros((op.shape[1],), op.dtype))
        pl = planner_mod.plan(shape_op, k, budget=budget, overrides=overrides)
        mu, S, Vt = _pca_centered_dense(
            op.array, jnp.asarray(seed, jnp.uint32), k, pl
        )
    else:
        cop = CenteredOp(op)
        mu = cop.mu
        _, S, Vt = svd(cop, k, overrides=overrides, budget=budget, seed=seed)
    return PCAResult(
        components=Vt,
        explained_variance=S**2 / (n - 1),
        singular_values=S,
        mean=mu,
    )


# ---------------------------------------------------------------------------
# Panel-wise residual: relative Frobenius error without an m x n temporary
# ---------------------------------------------------------------------------

@f32_matmuls
def residual(a, result: SVDResult, block_rows: Optional[int] = None) -> jax.Array:
    """||A - U S Vt||_F / ||A||_F accumulated one row panel at a time.

    The historical `core.rsvd.low_rank_error` materializes the full m x n
    reconstruction — fine in-core, impossible for a streamed/host source.
    This walks `op.row_panels()`: per panel only a (block_rows x n) residual
    exists, so HostOp sources report error at streaming residency.  3-D
    stacked sources reduce over every slice (flat Frobenius norm)."""
    U, S, Vt = result
    op = as_linop(a)
    if len(op.shape) == 3:
        # One vmapped pass collecting (||R_i||^2, ||A_i||^2) per slice —
        # summed before the divide, so an all-zero slice contributes 0/0-free
        # and the stack is read exactly once.
        A3 = jnp.asarray(op.array).astype(jnp.float32)

        def _slice_sq(Ai, Ui, Si, Vti):
            R = Ai - (Ui.astype(jnp.float32) * Si.astype(jnp.float32)[None, :]) \
                @ Vti.astype(jnp.float32)
            return jnp.sum(R * R), jnp.sum(Ai * Ai)

        nums, dens = jax.vmap(_slice_sq)(A3, U, S, Vt)
        return jnp.sqrt(jnp.sum(nums) / jnp.sum(dens))
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    lo = 0
    scaled_vt = (S[:, None] * Vt).astype(jnp.float32)          # (k, n), skinny
    # prefetched walk: host panel i+1 transfers while panel i's residual
    # GEMM runs — same panels, same order, same accumulation
    for panel in prefetch_panels(op, block_rows):
        hi = lo + panel.shape[0]
        P = panel.astype(jnp.float32)
        R = P - U[lo:hi].astype(jnp.float32) @ scaled_vt
        num = num + jnp.sum(R * R)
        den = den + jnp.sum(P * P)
        lo = hi
    if lo != op.shape[0]:
        raise ValueError(f"row_panels covered {lo} of {op.shape[0]} rows")
    return jnp.sqrt(num / den)
