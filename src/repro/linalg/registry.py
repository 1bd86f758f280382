"""The decomposition registry: `kind -> how to finish a solve`.

`linalg.decompose(source, spec, kind=...)` looks the kind up here.  Every
entry shares one engine — the (possibly adaptive) QB factorization from
core/adaptive.py, spec-driven — and differs only in how the revealed
factors are finished:

  svd    U = Q U_b, S, Vt              (Rank specs on array sources keep the
                                        historical fixed-rank executors —
                                        bit-identical to `linalg.svd`)
  qb     Q' = Q U_b[:, :r], B' = S Vt  (rank-revealed orthonormal basis)
  eigh   Nystrom for PSD sources:      A ~= F F^T,  F = (A Q) R^{-1},
                                        R^T R = Q^T A Q (floor-shifted
                                        Cholesky), eigpairs from svd(F)
  lu     randomized LU (Shabat et al. 2013 via the QB core):
                                        A[pr][:, pc] ~= L @ U with L m x r
                                        lower-trapezoidal, U r x n upper-
                                        trapezoidal, from pivoted LUs of Q
                                        and of the r x n middle factor
  pca    svd over the CenteredOp       (components / explained variance;
                                        Energy(p) is the explained-variance
                                        contract)

A handler returns ``(factors, rank, rank_history, err_history)``; the
facade wraps that in a `Decomposition`.  Third parties can add kinds with
`register(DecompositionKind(...))` — the planner validates requested kinds
against `kinds()` at plan time.
"""
from __future__ import annotations

import collections
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qr as qr_mod
from repro.core.rsvd import _small_svd
from repro.linalg import trace
from repro.linalg.spec import Rank, Spec

#: (factors, rank, rank_history, err_history)
HandlerResult = Tuple[tuple, int, Tuple[int, ...], Tuple[float, ...]]


@dataclass(frozen=True)
class DecompositionKind:
    """One registry entry.  `execute` finishes the solve; `prepare` (optional)
    transforms the source BEFORE planning (pca wraps in CenteredOp here, so
    the plan sees the operator that actually runs).  `ortho_factor`
    (optional) maps a handler's `factors` tuple to the matrix whose columns
    should be orthonormal — the guard's retry ladder verifies
    ||QᵀQ - I||_F on it (linalg/guard.py); None skips verification (lu,
    third-party kinds without an orthonormal factor)."""

    name: str
    execute: Callable  # (op, spec, plan, seed) -> HandlerResult
    prepare: Optional[Callable] = None  # (op) -> op
    description: str = ""
    ortho_factor: Optional[Callable] = None  # (factors) -> matrix | None


# Mutated by register() only (import time + third-party extensions), but
# extensions may register while service workers read — hence the lock.
_REGISTRY: Dict[str, DecompositionKind] = {}
_registry_write_lock = threading.Lock()


def register(entry: DecompositionKind) -> DecompositionKind:
    """Add (or replace) a decomposition kind.  Thread-safe: a service worker
    resolving kinds mid-`register` sees either the old or the new entry,
    never a torn table."""
    with _registry_write_lock:
        _REGISTRY[entry.name] = entry
    return entry


def get(name: str) -> DecompositionKind:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown decomposition kind {name!r}; registered kinds: "
            f"{', '.join(_REGISTRY)}"
        ) from None


def kinds() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


# ---------------------------------------------------------------------------
# LRU plan cache
#
# Planning is pure given (source fingerprint, spec, kind, budget, overrides,
# guard, validate, backend) — everything the planner reads off a source is
# shape/dtype/residency metadata, never data — yet `decompose()` re-planned
# on every call.  The fingerprints below are hashable, so identical repeat
# calls (the serving hot path: same layer shapes, same spec, thousands of
# requests) reuse the frozen ExecutionPlan instead of re-walking the
# autotune tables and the roofline model.  Sources whose planning inputs
# cannot be fingerprinted safely (sharded meshes, protocol-only operators)
# BYPASS the cache — correctness first, the cache is an optimization.
# ---------------------------------------------------------------------------

PLAN_CACHE_SIZE = 256

_plan_cache: "collections.OrderedDict" = collections.OrderedDict()
_plan_cache_lock = threading.Lock()  # decompose() is called from service threads
_PLAN_CACHE_OUTCOMES = ("hits", "misses", "bypasses")


def _op_fingerprint(op):
    """Hashable token covering every source attribute the planner reads, or
    None when this source kind can't be fingerprinted safely.

    Composed/transposed wrappers contribute their typenames (the planner
    only dispatches on them and peels to the base); the base contributes
    shape, dtype, residency (host numpy vs device — `_host_rooted` and the
    dense/streamed split read it), block_rows, pipeline_depth, and nnz."""
    from repro.linalg import operators as ops_mod

    parts = []
    depth = 0
    while isinstance(op, (ops_mod.ComposedOp, ops_mod._TransposedOp)):
        parts.append(type(op).__name__)
        op = op.base if isinstance(op, ops_mod.ComposedOp) else op._op
        depth += 1
        if depth > 32:
            return None
    if op.sharding is not None:
        return None  # mesh identity is not worth fingerprinting
    if type(op) not in (ops_mod.DenseOp, ops_mod.HostOp, ops_mod.StackedOp,
                        ops_mod.SparseOp):
        return None  # protocol-only / third-party sources: bypass
    arr = getattr(op, "array", None)
    parts.append((
        type(op).__name__,
        tuple(op.shape),
        jnp.dtype(op.dtype).name,
        isinstance(arr, np.ndarray),          # residency drives path choice
        op.block_rows,
        op.pipeline_depth,
        getattr(op, "nnz", None) if type(op) is ops_mod.SparseOp else None,
    ))
    return tuple(parts)


def cached_plan(op, spec, budget=None, overrides=None, kind: str = "svd",
                nnz=None, guard=None, validate: bool = False):
    """`planner.plan` behind a size-bounded LRU keyed on the already-hashable
    inputs.  Semantically transparent: a hit returns the SAME frozen
    ExecutionPlan a fresh plan() call would build (plans carry no data), and
    un-fingerprintable sources fall through to planner.plan untouched."""
    with trace.span("linalg.plan"):
        from repro.linalg import guard as guard_mod
        from repro.linalg import planner as planner_mod
        from repro.linalg import spec as spec_mod

        spec = spec_mod.as_spec(spec)
        guard = guard_mod.as_guard(guard)
        token = _op_fingerprint(op)
        if token is None:
            trace.add("plan_cache.bypasses")
            return planner_mod.plan(op, spec, budget=budget, overrides=overrides,
                                    kind=kind, nnz=nnz, guard=guard,
                                    validate=validate)
        key = (token, spec, kind, budget, overrides, nnz, guard, bool(validate),
               jax.default_backend())
        with _plan_cache_lock:
            pl = _plan_cache.get(key)
            if pl is not None:
                _plan_cache.move_to_end(key)
                trace.add("plan_cache.hits")
                return pl
            trace.add("plan_cache.misses")
        pl = planner_mod.plan(op, spec, budget=budget, overrides=overrides,
                              kind=kind, nnz=nnz, guard=guard, validate=validate)
        with _plan_cache_lock:
            _plan_cache[key] = pl
            while len(_plan_cache) > PLAN_CACHE_SIZE:
                _plan_cache.popitem(last=False)
        return pl


def plan_cache_stats() -> Dict[str, int]:
    """hits / misses / bypasses of the plan cache (the `plan_cache.*`
    counters of linalg/trace.py) and its present size."""
    stats = {k: trace.count(f"plan_cache.{k}") for k in _PLAN_CACHE_OUTCOMES}
    with _plan_cache_lock:
        return dict(stats, size=len(_plan_cache))


def clear_plan_cache() -> None:
    with _plan_cache_lock:
        _plan_cache.clear()
    trace.reset("plan_cache.")


# ---------------------------------------------------------------------------
# Shared engine: spec-driven QB + rank reveal
# ---------------------------------------------------------------------------

def _qb_core(op, spec: Spec, pl, seed):
    """Run the (adaptive) QB engine under the plan's switches.  The plan's
    `panel` / `k` carry the growth schedule (single `s`-wide panel for Rank
    specs); `threshold_sq` comes from the spec's stopping contract.  Rank
    specs need no stopping estimator, so they skip the ||A||_F^2 pass —
    one fewer read of A on the fixed-rank qb/lu/eigh paths.

    The whole growth runs under the plan's `pipeline_depth` as the ambient
    prefetch scope: host-rooted sources double-buffer every touch of A
    (matmat / rmatmat / the norm walk) without core/adaptive.py knowing the
    pipeline exists, and an early stop abandons in-flight prefetch cleanly."""
    from repro.core import adaptive
    from repro.linalg import pipeline

    with pipeline.default_depth(pl.pipeline_depth):
        norm_sq = threshold_sq = None
        if not isinstance(spec, Rank):
            norm_sq = adaptive.fro_norm_sq(op)
            threshold_sq = spec.threshold_sq(norm_sq)
        return adaptive.adaptive_qb(
            op,
            panel=pl.panel or pl.s,
            max_rank=pl.k,
            threshold_sq=threshold_sq,
            seed=seed,
            power_iters=pl.power_iters,
            qr_method=pl.qr_method,
            sketch_kind=pl.sketch_kind,
            fused_sketch=pl.fused_sketch,
            kernel_backend=pl.kernel_backend,
            norm_sq=norm_sq,
        )


def _reveal(qb, spec: Spec, pl):
    """Small SVD of B reveals the spectrum; the spec trims the rank (the
    ±panel overshoot of blocked growth, or the oversampling of a Rank
    spec's single panel)."""
    U_b, S, Vt = _small_svd(qb.B, pl.small_svd)
    keep = spec.select_rank(np.asarray(S), qb.remaining_sq or 0.0,
                            qb.norm_sq or 0.0)
    return U_b, S, Vt, int(keep)


# ---------------------------------------------------------------------------
# Handlers
# ---------------------------------------------------------------------------

def _execute_svd(op, spec, pl, seed) -> HandlerResult:
    if pl.path != "adaptive":
        # Rank spec on an array source: the historical fixed-rank executors,
        # bit-identical to pre-spec `linalg.svd` at fixed seed.
        from repro.linalg import api

        factors = api._execute_svd_plan(op, spec.k, pl, seed)
        return tuple(factors), spec.k, (spec.k,), ()
    qb = _qb_core(op, spec, pl, seed)
    U_b, S, Vt, keep = _reveal(qb, spec, pl)
    U = qb.Q @ U_b[:, :keep]
    return (U, S[:keep], Vt[:keep, :]), keep, qb.rank_history, qb.err_history


def _execute_qb(op, spec, pl, seed) -> HandlerResult:
    qb = _qb_core(op, spec, pl, seed)
    U_b, S, Vt, keep = _reveal(qb, spec, pl)
    Qk = qb.Q @ U_b[:, :keep]
    Bk = S[:keep, None] * Vt[:keep, :]
    return (Qk, Bk), keep, qb.rank_history, qb.err_history


def _execute_eigh(op, spec, pl, seed) -> HandlerResult:
    """Nystrom eigendecomposition for a PSD source: one extra pass over A
    (C = A Q) beyond the QB growth, everything else sketch-width."""
    qb = _qb_core(op, spec, pl, seed)
    U_b, S, Vt, keep = _reveal(qb, spec, pl)
    fdtype = jnp.promote_types(op.dtype, jnp.float32)
    Qk = (qb.Q @ U_b[:, :keep]).astype(fdtype)
    with qr_mod.kernel_backend(pl.kernel_backend):
        C = op.matmat(Qk).astype(fdtype)        # A Q, n x keep
        T = Qk.T @ C                            # Q^T A Q, keep x keep
        T = 0.5 * (T + T.T)
        # floor-shifted Cholesky (qr.cholesky_r_from_gram): indefinite noise
        # from a nearly-PSD source perturbs R at the eps level only
        R = qr_mod.cholesky_r_from_gram(T)
        F = qr_mod.tri_solve_right(C, R)        # A_nys = F F^T
    Uf, sf, _ = jnp.linalg.svd(F, full_matrices=False)
    w = sf**2                                   # descending eigenvalues
    return (w, Uf), keep, qb.rank_history, qb.err_history


def _execute_lu(op, spec, pl, seed) -> HandlerResult:
    """Randomized LU via the QB core: pivoted LU of the revealed basis Q,
    then of the r x n middle factor, composed so that

        A[perm_rows][:, perm_cols] ~= L @ U

    with L (m x r) lower-trapezoidal and U (r x n) unit-upper-trapezoidal —
    the two-sided permutation structure of Shabat et al. 2013, with the
    sketch stage replaced by the spec-driven (adaptive) basis."""
    from jax.lax import linalg as lax_linalg

    qb = _qb_core(op, spec, pl, seed)
    U_b, S, Vt, keep = _reveal(qb, spec, pl)
    fdtype = jnp.promote_types(op.dtype, jnp.float32)
    m, n = op.shape
    r = keep
    Qk = (qb.Q @ U_b[:, :r]).astype(fdtype)            # m x r, orthonormal
    Bk = (S[:r, None] * Vt[:r, :]).astype(fdtype)      # r x n
    lu1, _, perm_rows = lax_linalg.lu(Qk)              # Qk[perm] = L1 U1
    L1 = jnp.tril(lu1, -1) + jnp.eye(m, r, dtype=fdtype)
    U1 = jnp.triu(lu1[:r, :])
    mid = U1 @ Bk                                      # r x n
    lu2, _, perm_cols = lax_linalg.lu(mid.T)           # mid.T[perm] = L2 U2
    L2 = jnp.tril(lu2, -1) + jnp.eye(n, r, dtype=fdtype)
    U2 = jnp.triu(lu2[:r, :])
    # A[pr] ~= L1 (U1 Bk) = L1 mid;  mid[:, pc] = U2^T L2^T
    L = L1 @ U2.T                                      # lower-trapezoidal
    U = L2.T                                           # unit-upper-trapezoidal
    return (perm_rows, L, U, perm_cols), keep, qb.rank_history, qb.err_history


def _prepare_pca(op):
    from repro.linalg.operators import CenteredOp

    return op if isinstance(op, CenteredOp) else CenteredOp(op)


def _execute_pca(op, spec, pl, seed) -> HandlerResult:
    """PCA = svd of the CenteredOp (`prepare` wrapped it).  Factors follow
    `core.pca.PCAResult` field order: (components, explained_variance,
    singular_values, mean)."""
    (U, S, Vt), keep, rank_hist, err_hist = _execute_svd(op, spec, pl, seed)
    n = op.shape[0]
    return (Vt, S**2 / (n - 1), S, op.mu), keep, rank_hist, err_hist


def _batched_safe(factor):
    """Guard verification targets a single 2-D factor; batched (3-D)
    factors are skipped (the probes still cover them — every vmapped slice
    reports through the probed twin)."""
    return None if getattr(factor, "ndim", 2) == 3 else factor


register(DecompositionKind(
    "svd", _execute_svd,
    description="U S Vt; Rank specs keep the historical fixed-rank paths",
    ortho_factor=lambda f: _batched_safe(f[0])))          # U: m x k
register(DecompositionKind(
    "qb", _execute_qb,
    description="rank-revealed orthonormal basis: A ~= Q B",
    ortho_factor=lambda f: f[0]))                         # Q: m x r
register(DecompositionKind(
    "eigh", _execute_eigh,
    description="Nystrom eigendecomposition of a PSD source: A ~= V diag(w) V^T",
    ortho_factor=lambda f: f[1]))                         # V: n x r
register(DecompositionKind(
    "lu", _execute_lu,
    description="randomized LU: A[pr][:, pc] ~= L U (Shabat et al. 2013)"))
register(DecompositionKind(
    "pca", _execute_pca, prepare=_prepare_pca,
    description="PCA over the centered operator; Energy(p) = explained variance",
    ortho_factor=lambda f: f[0].T))                       # componentsᵀ: d x r
