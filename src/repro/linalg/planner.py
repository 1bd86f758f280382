"""Execution planner: `plan(op, k, budget) -> ExecutionPlan`.

Replaces the ad-hoc `if` ladder that used to live in `core.rsvd.randomized_svd`
plus the hand-tuned `RSVDConfig` execution switches (`fused_power`,
`kernel_backend`, `block_rows`, `batched`).  The planner inspects the
operator source (shape, dtype, residency, sharding), the device, the VMEM /
HBM budget, and the `kernels/autotune.py` block-size cache, and emits an
inspectable `ExecutionPlan` that `linalg.svd / eigvals / pca` execute.

`RSVDConfig` survives as a thin frozen view for explicit overrides: passing
`overrides=RSVDConfig...` reproduces the pre-planner dispatch decisions
bit-for-bit (the presets `faithful()` / `fast()` / `streaming()` map onto
plans 1:1), with the same VMEM gate the dense body applies — so a plan's
`fused_power` field is the EFFECTIVE decision, never an unhonored request.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rsvd import RSVDConfig
from repro.linalg import guard as guard_mod
from repro.linalg import operators as ops_mod
from repro.linalg import pipeline as pipeline_mod
from repro.linalg import spec as spec_mod
from repro.linalg.operators import LinOp, as_linop
from repro.linalg.spec import Rank, Spec
from repro.roofline import rsvd_model

#: execution paths the planner can choose
PATHS = ("dense", "streamed", "batched", "sharded", "matfree", "adaptive", "sparse")


@dataclass(frozen=True)
class Budget:
    """Hardware envelope the planner fits a solve into.

    Unset fields resolve to the single source of truth, roofline/hw.py:
    the VMEM the fused kernels compile with and the HBM size — so a
    partially-specified Budget can never freeze a stale copy of either
    constant.  `vmem_bytes` can only TIGHTEN the fusion gate: the fused
    body re-checks the compiled-in limit at trace time, so a plan claiming
    fusion past it would lie about what executes (see
    `_effective_fused_power`)."""

    vmem_bytes: Optional[int] = None
    hbm_bytes: Optional[int] = None

    def __post_init__(self):
        from repro.roofline import hw

        if self.vmem_bytes is None:
            object.__setattr__(self, "vmem_bytes", hw.VMEM_BYTES)
        if self.hbm_bytes is None:
            object.__setattr__(self, "hbm_bytes", hw.HBM_BYTES)

    @staticmethod
    def default() -> "Budget":
        return Budget()


@dataclass(frozen=True)
class ExecutionPlan:
    """The planner's full decision record — every field the executor reads,
    plus the roofline prediction, so a plan is inspectable and loggable
    (benchmarks/bench_rsvd.py persists executed plans to BENCH_rsvd.json)."""

    path: str                      # dense | streamed | batched | sharded | matfree | adaptive | sparse
    m: int                         # post-orientation tall dim (m >= n); adaptive
    n: int                         # plans record the EXECUTED (source) orientation
    k: int
    s: int                         # sketch width = min(k + oversample, n)
    batch: int                     # leading batch dim (1 unless path=batched)
    dtype: str
    # numerical variant (Algorithm 1 switches)
    oversample: int
    power_iters: int
    power_scheme: str
    qr_method: str
    small_svd: str
    sketch_kind: str
    # execution switches (all EFFECTIVE — gates already applied)
    fused_sketch: bool
    fused_power: bool
    kernel_backend: str
    block_rows: Optional[int]
    block_cols: Optional[int]
    blocks: Tuple[int, int, int]   # (bm, bn, bk) the kernels will tile with
    predicted_hbm_bytes: int       # roofline/rsvd_model.py whole-solve bytes
    # spec-driven decomposition fields (PR 4): what the caller asked for and,
    # for adaptive (fixed-precision) plans, the planned rank growth.  For
    # Rank specs, k above IS the target; for Tolerance/Energy, k records the
    # max-rank cap (the full-rank fallback) and s the growth-panel sketch
    # width.
    kind: str = "svd"                           # registry entry to execute
    spec: Optional[Spec] = None                 # the accuracy contract
    panel: Optional[int] = None                 # adaptive growth-panel width
    rank_schedule: Tuple[int, ...] = ()         # planned cumulative basis sizes
    schedule_hbm_bytes: Tuple[int, ...] = ()    # roofline bytes per growth step
    # out-of-core pipeline fields (PR 5): how deep the panel prefetch runs
    # (1 = fully synchronous — the pre-pipeline behavior) and the overlap-
    # aware walltime prediction (rsvd_model.streamed_walltime_s for streamed
    # plans, plain HBM-bandwidth time elsewhere).
    pipeline_depth: int = 1
    predicted_walltime_s: float = 0.0
    # sparse-source fields (PR 6): stored nonzeros and density of the solve's
    # base operator.  Set whenever the source (possibly under a composition)
    # is a SparseOp — the traffic prediction then prices every read of A at
    # nnz * (value + index) bytes (rsvd_model.sparse_* functions).
    nnz: Optional[int] = None
    density: Optional[float] = None
    # guarded-execution fields (PR 7): how the executor watches / recovers
    # this solve (linalg/guard.py) and whether input is screened for
    # non-finite values up front.  Neither changes the numerics of a
    # healthy solve: guard "off" and validate=False are the pre-guard
    # behavior bit-for-bit, and "report" only adds probe reductions on
    # byproducts (no extra reads of A — predicted_hbm_bytes is unchanged).
    guard: guard_mod.GuardPolicy = guard_mod.GuardPolicy()
    validate: bool = False

    def fingerprint(self) -> str:
        """Stable string identity of the numerics this plan executes — the
        fields that determine the op sequence at fixed seed (prediction
        fields and guard/validate knobs excluded: they never change a
        healthy solve's bytes).  Job-store manifests persist this so a
        restored service only resumes a job whose re-planned execution is
        the one the snapshot came from."""
        return "|".join(str(x) for x in (
            self.path, self.m, self.n, self.k, self.s, self.batch,
            self.dtype, self.oversample, self.power_iters, self.power_scheme,
            self.qr_method, self.small_svd, self.sketch_kind,
            self.fused_sketch, self.fused_power, self.kernel_backend,
            self.block_rows, self.block_cols, self.kind, self.panel,
            self.pipeline_depth, self.nnz))

    def to_config(self) -> RSVDConfig:
        """The thin frozen RSVDConfig view the core numerics execute."""
        return RSVDConfig(
            oversample=self.oversample,
            power_iters=self.power_iters,
            power_scheme=self.power_scheme,
            qr_method=self.qr_method,
            small_svd=self.small_svd,
            sketch_kind=self.sketch_kind,
            fused_sketch=self.fused_sketch,
            fused_power=self.fused_power,
            kernel_backend=self.kernel_backend,
            block_rows=self.block_rows if self.path == "streamed" else None,
            block_cols=self.block_cols,
            batched=self.path == "batched",
            pipeline_depth=self.pipeline_depth if self.path == "streamed" else None,
        )

    def describe(self) -> str:
        """One-line human summary (examples/quickstart.py prints this)."""
        shape = f"{self.batch}x{self.m}x{self.n}" if self.batch > 1 else f"{self.m}x{self.n}"
        spec_str = self.spec.describe() if self.spec is not None else f"rank(k={self.k})"
        bits = [
            f"path={self.path}", f"shape={shape}", f"k={self.k}", f"s={self.s}",
            f"kind={self.kind}", f"spec={spec_str}",
            f"qr={self.qr_method}", f"backend={self.kernel_backend}",
            f"fused_sketch={self.fused_sketch}", f"fused_power={self.fused_power}",
            f"pipeline_depth={self.pipeline_depth}",
        ]
        if self.guard.mode != "off":
            bits.append(f"guard={self.guard.mode}")
        if self.validate:
            bits.append("validate=on")
        if self.block_rows:
            bits.append(f"block_rows={self.block_rows}")
        if self.path == "adaptive":
            bits.append(f"panel={self.panel}")
            bits.append(f"steps={len(self.rank_schedule)}")
        if self.nnz is not None:
            bits.append(f"nnz={self.nnz}")
            bits.append(f"density={self.density:.4g}")
        bits.append(f"pred_hbm={self.predicted_hbm_bytes / 1e6:.1f}MB")
        return " ".join(bits)


def _is_f64(dtype) -> bool:
    return jnp.dtype(dtype) == jnp.float64


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick_path(op: LinOp, cfg: Optional[RSVDConfig]) -> str:
    """The dispatch ladder, now in one inspectable place.

    With explicit overrides this reproduces the historical
    `core.rsvd.randomized_svd` dispatch exactly: 3-D / `batched` -> batched,
    `block_rows` -> streamed, everything else dense (host numpy included —
    the old entry point moved it to device wholesale).  Without overrides
    the operator's residency decides: host-resident sources stream."""
    if isinstance(op, (ops_mod.ComposedOp, ops_mod._TransposedOp)):
        return "matfree"
    if op.sharding is not None:
        return "sharded"
    if len(op.shape) == 3:
        return "batched"
    if isinstance(op, ops_mod.SparseOp):
        # the sparse path IS the matfree operator body, named so the plan
        # (and its SpMM traffic pricing) is distinguishable and loggable
        return "sparse"
    if not isinstance(op, ops_mod.DenseOp):
        # protocol-only sources have no .array to hand the dense/streamed
        # executors — they run the generic operator body, overrides or not
        return "matfree"
    if cfg is not None:
        if cfg.batched:
            return "batched"
        if cfg.block_rows:
            return "streamed"
        # An explicitly constructed HostOp (or a block_rows-carrying source)
        # expresses out-of-core intent that numerical overrides must not
        # discard — moving the whole host array to device would defeat the
        # residency contract.  The deprecation shim wraps raw arrays in
        # DenseOp, so the historical wholesale-dense dispatch is unaffected.
        if isinstance(op, ops_mod.HostOp) or op.block_rows:
            return "streamed"
        return "dense"
    if isinstance(op, ops_mod.HostOp) or op.block_rows:
        return "streamed"
    return "dense"


def _default_config(op: LinOp, path: str, budget: Budget) -> RSVDConfig:
    """Planner defaults when the caller gives no overrides: device- and
    dtype-aware versions of the faithful/fast/streaming presets."""
    f64 = _is_f64(op.dtype)
    if path == "streamed":
        block = op.block_rows or ops_mod.HostOp.DEFAULT_BLOCK_ROWS
        # Shrink the panel until one panel + sketch-width state fits a
        # quarter of the HBM budget (leave room for Y/Q/U and the caller).
        # Panels are block_rows x n AFTER orientation (the streamed body
        # factors the taller side), so the row length is the SHORT dim.
        n = min(op.shape[-2], op.shape[-1])
        itemsize = jnp.dtype(op.dtype).itemsize
        while block > 256 and block * n * itemsize > budget.hbm_bytes // 4:
            block //= 2
        return dataclasses.replace(RSVDConfig.streaming(block_rows=block),
                                   fused_sketch=_on_tpu() and not f64,
                                   kernel_backend="pallas" if _on_tpu() and not f64 else "jnp")
    if f64:
        if path == "adaptive":
            # the adaptive body is CholeskyQR-shaped (deflation + CGS2); the
            # jnp backend keeps the faithful f64 precision end to end
            return RSVDConfig(power_scheme="stabilized", qr_method="cqr2",
                              small_svd="lapack")
        return RSVDConfig.faithful()  # the paper's dgesvd setting: jnp, no fusion
    if _on_tpu():
        if path == "dense":
            return RSVDConfig.fast()
        # batched / sharded / matfree: the CQR Gram+TRSM primitives route
        # through the Pallas kernels on every path that honors the backend
        return RSVDConfig(power_scheme="stabilized", qr_method="cqr2",
                          small_svd="lapack", fused_sketch=True,
                          kernel_backend="pallas")
    # CPU / interpret-mode hosts: the Pallas kernels are a correctness
    # harness there, not a perf mode — stay on the XLA GEMMs.
    return RSVDConfig(power_scheme="stabilized", qr_method="cqr2")


def _effective_fused_power(m: int, n: int, s: int, dtype, cfg: RSVDConfig,
                           path: str, budget: Budget) -> bool:
    """The dense body's fusion gate, evaluated at plan time.  Delegates to
    the SAME predicate the dense body uses (core.rsvd._use_fused_power,
    parameterized by the plan's VMEM budget) so plan and execution can
    never drift apart.  The budget is clamped to the kernels' compiled-in
    limit, hw.VMEM_BYTES: the body re-checks that constant at trace time,
    so a looser Budget must not make the plan claim a fusion that would not
    actually execute."""
    if path != "dense":
        return False  # vmap (batched) and panel/shard bodies never fuse power
    from repro.core.rsvd import _use_fused_power
    from repro.roofline import hw

    shape = jax.ShapeDtypeStruct((m, n), dtype)
    vmem = min(budget.vmem_bytes, hw.VMEM_BYTES)
    return _use_fused_power(shape, cfg, s, vmem_budget=vmem)


def _host_rooted(op: LinOp) -> bool:
    """Does the solve ultimately stream a HOST-resident array?  Composed /
    transposed operators are peeled down to their base: the transfers a
    CenteredOp-over-HostOp pays are the HostOp's."""
    while isinstance(op, (ops_mod.ComposedOp, ops_mod._TransposedOp)):
        op = op.base if isinstance(op, ops_mod.ComposedOp) else op._op
    return isinstance(getattr(op, "array", None), np.ndarray)


def _sparse_nnz(op: LinOp) -> Optional[int]:
    """Stored nonzeros of the solve's BASE operator, or None for dense
    sources.  Composed / transposed operators are peeled (a CenteredOp over
    a SparseOp still pays SpMM traffic for every read of A — the rank-one
    correction is O(s) extra, which the byte model drops)."""
    while isinstance(op, (ops_mod.ComposedOp, ops_mod._TransposedOp)):
        op = op.base if isinstance(op, ops_mod.ComposedOp) else op._op
    return op.nnz if isinstance(op, ops_mod.SparseOp) else None


def _apply_sketch_knobs(cfg: RSVDConfig, spec: Spec, path: str) -> RSVDConfig:
    """Resolve the sketch kind the solve will RUN: a spec-level `sketch=`
    knob overrides the config default, and structured kinds fall back to
    gaussian on the paths that regenerate row-offset sketch panels
    (streamed / sharded) — SRHT's column sample and CountSketch's buckets
    are global draws, not row-decomposable, so those bodies cannot stream
    them.  The returned config records what actually executes."""
    requested = getattr(spec, "sketch", None)
    if requested:
        cfg = dataclasses.replace(cfg, sketch_kind=requested)
    from repro.core import sketch as sketch_mod

    if cfg.sketch_kind in sketch_mod.STRUCTURED_KINDS and path in ("streamed", "sharded"):
        cfg = dataclasses.replace(cfg, sketch_kind="gaussian")
    return cfg


def _pick_pipeline_depth(cfg: Optional[RSVDConfig], m: int, n: int,
                         block_rows: int, itemsize: int,
                         budget: Budget,
                         source_depth: Optional[int] = None) -> int:
    """Prefetch depth for a panel-streaming plan, from the same quarter-HBM
    budget rule that sizes the panels: `depth` staging panels must be
    co-resident, so depth shrinks (down to 1 — synchronous) whenever
    depth * panel_bytes overflows the quarter budget a single panel was
    sized into.  An explicit cfg.pipeline_depth — else the source's own
    preference, mirroring the block_rows precedence — is the starting point
    (still budget- and panel-count-clamped: a plan must be executable);
    otherwise the default is double-buffered on real accelerators and 1 on
    the CPU backend, where no host link exists to overlap."""
    n_panels = -(-m // block_rows)  # ceil
    requested = (cfg.pipeline_depth if cfg is not None else None) or source_depth
    if requested:
        depth = min(requested, n_panels)
    elif jax.default_backend() == "cpu":
        return 1
    else:
        depth = min(pipeline_mod.DEFAULT_DEPTH, n_panels)
    panel_bytes = block_rows * n * itemsize
    while depth > 1 and depth * panel_bytes > budget.hbm_bytes // 4:
        depth -= 1
    return max(depth, 1)


def _validate(op: LinOp, spec: Spec, kind: str) -> None:
    """Facade-level input validation: bad ranks and unknown kinds fail HERE
    with a clear ValueError instead of deep inside the numerics."""
    from repro.linalg import registry

    registry.get(kind)  # unknown kinds raise registry's ValueError
    shape = op.shape
    rmax = min(shape[-2], shape[-1])
    if rmax == 0:
        raise ValueError(f"source has an empty dimension: shape {tuple(shape)}")
    if isinstance(spec, Rank):
        if spec.k <= 0:
            raise ValueError(f"rank k must be positive, got k={spec.k}")
        if spec.k > rmax:
            raise ValueError(
                f"rank k={spec.k} exceeds min(m, n)={rmax} for source shape "
                f"{tuple(shape)}"
            )
    elif len(shape) == 3:
        raise ValueError(
            f"adaptive spec {spec.describe()} needs a 2-D source, got shape "
            f"{tuple(shape)} (per-slice ranks would be ragged — solve slices "
            "individually or use a Rank spec)"
        )
    if kind == "eigh" and shape[-2] != shape[-1]:
        raise ValueError(
            f"kind='eigh' needs a square (PSD) source, got shape {tuple(shape)}"
        )
    if kind in _QB_KINDS and len(shape) == 3:
        raise ValueError(
            f"kind={kind!r} needs a 2-D source, got shape {tuple(shape)}"
        )


#: kinds that always execute through the QB engine (core/adaptive.py), even
#: under a Rank spec — their plan records the QB growth, not a dense solve
_QB_KINDS = ("qb", "eigh", "lu")


def _plan_adaptive(op: LinOp, spec: Spec, kind: str, budget: Budget,
                   overrides: Optional[RSVDConfig],
                   nnz: Optional[int] = None,
                   guard: guard_mod.GuardPolicy = guard_mod.GuardPolicy(),
                   validate: bool = False) -> ExecutionPlan:
    """Fixed-precision (Tolerance/Energy) plan: the rank is unknown, so the
    plan records the GROWTH SCHEDULE — cumulative basis sizes in autotune-
    sized panels up to the max-rank cap — and the roofline bytes of each
    step.  Execution (registry -> core/adaptive.py) stops early once the
    posterior estimator meets the spec; the executed prefix of the schedule
    is what actually runs.

    Unlike the fixed-rank paths, the QB engine does NOT transpose wide
    sources (qb/lu factor shapes are part of the caller's contract, and the
    basis approximates range(A), which is orientation-specific), so the
    plan records the EXECUTED orientation — m/n are the source dims as-is,
    and the roofline schedule (whose deflation/reorth terms scale with the
    basis length m) models the solve that actually runs."""
    from repro.kernels.ops import _block, _select_blocks

    shape = op.shape
    m, n = shape[-2], shape[-1]
    rmax = min(m, n)
    f64 = _is_f64(op.dtype)
    cfg = overrides if overrides is not None else _default_config(op, "adaptive", budget)
    cfg = _apply_sketch_knobs(cfg, spec, "adaptive")
    if nnz is None:
        nnz = _sparse_nnz(op)

    if isinstance(spec, Rank):
        # a _QB_KINDS entry at fixed rank: ONE oversampled panel, trimmed
        # back to k by the rank reveal
        cap = min(spec.k + cfg.oversample, rmax)
        panel = cap
    else:
        cap = min(getattr(spec, "max_rank", None) or rmax, rmax)
        panel = getattr(spec, "panel", None)
        if not panel:
            # autotune-sized growth panel: the sketch kernel's preferred s-tile
            panel = _select_blocks("sketch_matmul", (m, 128, n), op.dtype)[1]
        panel = max(1, min(panel, cap))

    from repro.core import sketch as sketch_mod

    # the fused in-VMEM sketch serves device-resident dense sources only
    # (HostOp subclasses DenseOp but streams from host — excluded by type);
    # structured kinds apply by transform, so there is no RNG tile to fuse
    fused_sketch = (
        bool(cfg.fused_sketch) and not f64 and type(op) is ops_mod.DenseOp
        and cfg.sketch_kind not in sketch_mod.STRUCTURED_KINDS
    )
    backend = "jnp" if f64 else cfg.kernel_backend

    steps = -(-cap // panel)  # ceil
    rank_schedule = tuple(min((i + 1) * panel, cap) for i in range(steps))
    dtype_bytes = jnp.dtype(op.dtype).itemsize
    schedule_bytes = rsvd_model.adaptive_schedule_bytes(
        m, n, rank_schedule, cfg.power_iters,
        dtype_bytes=dtype_bytes, fused_sketch=fused_sketch, nnz=nnz,
    )
    if fused_sketch:
        bm_, bn_, bk_ = _select_blocks("sketch_matmul", (m, panel, n), op.dtype)
        blocks = (bm_, min(bn_, _block(panel)), bk_)
    else:
        blocks = _select_blocks("matmul", (m, n, panel), op.dtype)

    # Host-rooted sources stream their matmat/rmatmat (and the ||A||_F^2
    # walk) through the prefetch pipeline at this depth — the registry sets
    # it as the ambient pipeline.default_depth around the growth loop.
    pipeline_depth = 1
    if _host_rooted(op):
        stream_block = op.block_rows or ops_mod.HostOp.DEFAULT_BLOCK_ROWS
        pipeline_depth = _pick_pipeline_depth(
            overrides, m, n, stream_block, dtype_bytes, budget,
            source_depth=op.pipeline_depth,
        )

    return ExecutionPlan(
        path="adaptive",
        m=m, n=n, k=cap, s=panel, batch=1,
        dtype=jnp.dtype(op.dtype).name,
        oversample=cfg.oversample,
        power_iters=cfg.power_iters,
        power_scheme=cfg.power_scheme,
        qr_method=cfg.qr_method,
        small_svd=cfg.small_svd,
        sketch_kind=cfg.sketch_kind,
        fused_sketch=fused_sketch,
        fused_power=False,          # the growth loop never fuses the power step
        kernel_backend=backend,
        block_rows=None,
        block_cols=cfg.block_cols,
        blocks=tuple(blocks),
        predicted_hbm_bytes=sum(schedule_bytes),
        kind=kind,
        spec=spec,
        panel=panel,
        rank_schedule=rank_schedule,
        schedule_hbm_bytes=schedule_bytes,
        pipeline_depth=pipeline_depth,
        predicted_walltime_s=rsvd_model.hbm_walltime_s(sum(schedule_bytes)),
        nnz=nnz,
        density=None if nnz is None else nnz / float(m * n),
        guard=guard,
        validate=validate,
    )


def plan(
    op,
    spec,
    budget: Optional[Budget] = None,
    overrides: Optional[RSVDConfig] = None,
    kind: str = "svd",
    nnz: Optional[int] = None,
    guard=None,
    validate: bool = False,
) -> ExecutionPlan:
    """Build the execution plan for a solve over `op`.

    `spec` is a rank (int, the historical signature) or an accuracy `Spec`
    (`Rank`/`Tolerance`/`Energy`).  Shape-only: `op` may wrap a
    `jax.ShapeDtypeStruct` — nothing is computed or moved here.  `overrides`
    pins the numerical variant and the historical dispatch; otherwise the
    planner picks device-appropriate defaults per source kind.  `kind`
    names the decomposition-registry entry the plan targets (svd, eigh, qb,
    lu, pca).  `nnz` declares the source's stored-nonzero count for the
    SpMM traffic pricing — it defaults from the operator itself (SparseOp,
    possibly under a composition), and the explicit argument serves
    shape-only planning where no data exists to count.  `guard` (a mode
    string or GuardPolicy) and `validate` set the guarded-execution fields
    — see linalg/guard.py; both default to the unguarded pre-guard
    behavior."""
    op = as_linop(op)
    budget = budget or Budget.default()
    spec = spec_mod.as_spec(spec)
    guard = guard_mod.as_guard(guard)
    _validate(op, spec, kind)
    if nnz is None:
        nnz = _sparse_nnz(op)
    if not isinstance(spec, Rank) or kind in _QB_KINDS:
        return _plan_adaptive(op, spec, kind, budget, overrides, nnz=nnz,
                              guard=guard, validate=validate)
    k = spec.k
    path = _pick_path(op, overrides)
    cfg = overrides if overrides is not None else _default_config(op, path, budget)
    cfg = _apply_sketch_knobs(cfg, spec, path)

    shape = op.shape
    batch = shape[0] if len(shape) == 3 else 1
    m_raw, n_raw = shape[-2], shape[-1]
    m, n = (m_raw, n_raw) if m_raw >= n_raw else (n_raw, m_raw)  # tall orientation
    s = min(k + cfg.oversample, n)

    from repro.core import sketch as sketch_mod

    fused_power = _effective_fused_power(m, n, s, op.dtype, cfg, path, budget)
    fused_sketch = (
        bool(cfg.fused_sketch)
        and not _is_f64(op.dtype)
        and path not in ("matfree", "sharded")  # shard body materializes Omega
        # structured kinds apply by transform — no RNG tile to generate
        and cfg.sketch_kind not in sketch_mod.STRUCTURED_KINDS
    )
    # float64 always takes the jnp primitives (qr._use_pallas vetoes the
    # fp32-accumulating kernels) — record the backend that actually runs.
    backend = "jnp" if _is_f64(op.dtype) else cfg.kernel_backend
    power_scheme, qr_method, small_svd = cfg.power_scheme, cfg.qr_method, cfg.small_svd
    if path == "sharded":
        # The shard_map body hardcodes its variant — a CQR2 stabilized loop,
        # replicated LAPACK small SVD, per-shard regenerated Omega
        # (core/distributed.py); the plan records THAT, not the overrides'
        # wishes, so BENCH rows and describe() never misreport execution.
        power_scheme, qr_method, small_svd = "stabilized", "cqr2", "lapack"

    from repro.kernels.ops import _block, _select_blocks

    # Mirror the EXACT (kernel, shape-order, clamp) lookups the wrappers
    # perform (ops.power_step uses (m, n, s); ops.sketch_matmul uses
    # (m, s, n) and clamps bn to the sketch width) so the recorded tiles
    # are the ones that will actually run.
    if path == "sparse" and fused_sketch:
        # the SpMM-sketch kernel's tiling — the (bm, bk) pair also keys the
        # block-ELL pack SparseOp caches (ops.spmm_blocks does this lookup)
        blocks = _select_blocks("spmm_sketch", (m, s, n), op.dtype)
    elif fused_power:
        blocks = _select_blocks("power_step", (m, n, s), op.dtype)
    elif fused_sketch:
        bm_, bn_, bk_ = _select_blocks("sketch_matmul", (m, s, n), op.dtype)
        blocks = (bm_, min(bn_, _block(s)), bk_)
    else:
        blocks = _select_blocks("matmul", (m, n, s), op.dtype)

    if nnz is not None and path in ("sparse", "matfree"):
        # every read of A is an SpMM at nnz * (value + index) bytes — the
        # solve the matfree operator body actually runs over a sparse base
        predicted = rsvd_model.sparse_predicted_hbm_bytes(
            m, n, s,
            power_iters=cfg.power_iters,
            nnz=nnz,
            fused_sketch=fused_sketch,
            dtype_bytes=jnp.dtype(op.dtype).itemsize,
        )
    else:
        predicted = rsvd_model.predicted_hbm_bytes(
            m, n, s,
            power_iters=cfg.power_iters,
            fused_power=fused_power,
            fused_sketch=fused_sketch,
            dtype_bytes=jnp.dtype(op.dtype).itemsize,
            batch=batch,
        )

    block_rows = None
    pipeline_depth = 1
    if path == "streamed":
        # cfg's explicit panel height wins; else the source's; else the
        # streaming default (so a streamed plan is always executable).
        block_rows = cfg.block_rows or op.block_rows or ops_mod.HostOp.DEFAULT_BLOCK_ROWS
        pipeline_depth = _pick_pipeline_depth(
            cfg, m, n, block_rows, jnp.dtype(op.dtype).itemsize, budget,
            source_depth=op.pipeline_depth,
        )
        predicted_walltime = rsvd_model.streamed_walltime_s(
            m, n, s, block_rows, cfg.power_iters, pipeline_depth,
            dtype_bytes=jnp.dtype(op.dtype).itemsize, fused_sketch=fused_sketch,
        )
    elif path == "matfree" and _host_rooted(op):
        # composed-over-host sources stream underneath the operator products;
        # record the depth their prefetched base walk resolves to
        pipeline_depth = _pick_pipeline_depth(
            cfg, m, n, op.block_rows or ops_mod.HostOp.DEFAULT_BLOCK_ROWS,
            jnp.dtype(op.dtype).itemsize, budget,
            source_depth=op.pipeline_depth,
        )
        predicted_walltime = rsvd_model.hbm_walltime_s(predicted)
    else:
        predicted_walltime = rsvd_model.hbm_walltime_s(predicted)

    return ExecutionPlan(
        path=path,
        m=m, n=n, k=k, s=s, batch=batch,
        dtype=jnp.dtype(op.dtype).name,
        oversample=cfg.oversample,
        power_iters=cfg.power_iters,
        power_scheme=power_scheme,
        qr_method=qr_method,
        small_svd=small_svd,
        sketch_kind=cfg.sketch_kind,
        fused_sketch=fused_sketch,
        fused_power=fused_power,
        kernel_backend=backend,
        block_rows=block_rows,
        block_cols=cfg.block_cols,
        blocks=tuple(blocks),
        predicted_hbm_bytes=predicted,
        kind=kind,
        spec=spec,
        rank_schedule=(k,),
        pipeline_depth=pipeline_depth,
        predicted_walltime_s=predicted_walltime,
        nnz=nnz,
        density=None if nnz is None else nnz / float(m * n),
        guard=guard,
        validate=validate,
    )
