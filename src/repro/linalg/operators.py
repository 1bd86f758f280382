"""Operator sources for the randomized-SVD facade (`repro.linalg`).

The paper's core claim is that randomized SVD becomes hardware-fast when
every step is phrased as BLAS-3 over *whatever form the data arrives in*.
`LinOp` is that form-contract: an operator exposes its shape/dtype, the two
products the range finder needs (``matmat`` = A @ X, ``rmatmat`` = Aᵀ @ Y),
and optionally a ``row_panels()`` iterator (out-of-core streaming, panel-wise
residuals) and a ``sharding`` spec (mesh execution).  The execution planner
(planner.py) dispatches on the source; the algorithm never sees anything but
this protocol.

Concrete sources:
  DenseOp    device-resident 2-D array             -> dense in-memory path
  HostOp     host (numpy) 2-D array, panel-streamed -> blocked/streaming path
  StackedOp  3-D batch [B, m, n]                   -> batched vmap path
  ShardedOp  row-sharded array on a device mesh    -> shard_map path

Composed operators (the new workload class — nothing is materialized):
  ScaledOp          alpha * A
  CenteredOp        A - 1 muᵀ    (PCA without forming the centered matrix)
  LowRankUpdateOp   A + U Vᵀ     (deflation: A - U_k S_k V_kᵀ as an operator)

`prefetch_panels(op, block_rows, depth)` is the overlapped edition of
`row_panels`: host->device movement of panel i+1 is issued while panel i
computes (linalg/pipeline.py), bit-identical values in the same order.
"""
from __future__ import annotations

from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

try:  # scipy ships with the jax toolchain, but SparseOp must not require it
    import scipy.sparse as _scipy_sparse
except ImportError:  # pragma: no cover - scipy is present in the image
    _scipy_sparse = None

from repro.linalg import pipeline as pipeline_mod
from repro.linalg import trace


class LinOp:
    """Operator-source protocol.  Subclasses must provide `shape`, `dtype`,
    `matmat`, and `rmatmat`; `row_panels` / `sharding` are optional extras
    the planner and panel-wise consumers (linalg.residual) exploit."""

    #: (mesh, axis) for mesh-resident operators, else None.
    sharding: Optional[Tuple[jax.sharding.Mesh, str]] = None
    #: preferred row-panel height for streamed execution, else None.
    block_rows: Optional[int] = None
    #: preferred prefetch depth for panel walks, else None (auto: the
    #: pipeline default for host-resident sources, 1 otherwise).
    pipeline_depth: Optional[int] = None

    @property
    def shape(self) -> Tuple[int, ...]:
        raise NotImplementedError

    @property
    def dtype(self):
        raise NotImplementedError

    def matmat(self, X: jax.Array) -> jax.Array:
        """A @ X  (X is n x s, sketch-width)."""
        raise NotImplementedError

    def rmatmat(self, Y: jax.Array) -> jax.Array:
        """Aᵀ @ Y  (Y is m x s, sketch-width)."""
        raise NotImplementedError

    def row_panels(self, block_rows: Optional[int] = None) -> Iterator[jax.Array]:
        """Device-resident row panels covering A top-to-bottom.

        The default materializes panel slices of the dense form; sources
        with a cheaper panel story (HostOp: host slices moved one at a
        time) override it.  Composed operators compose panel-wise, so a
        CenteredOp over a HostOp still never forms the full matrix."""
        m = self.shape[0]
        b = block_rows or self.block_rows or m
        eye_dtype = jnp.promote_types(self.dtype, jnp.float32)
        for lo in range(0, m, b):
            hi = min(lo + b, m)
            # A[lo:hi] = (E_panelᵀ A)ᵀ through rmatmat.  E is the sliced
            # standard basis e_lo..e_{hi-1} — an offset-diagonal eye (iota
            # comparison), NOT an m-sized scatter per panel; entries are
            # exact 0/1 either way so the panel values are bit-identical.
            e = jnp.eye(m, hi - lo, -lo, dtype=eye_dtype)
            yield self.rmatmat(e).T.astype(self.dtype)

    def prefetch_panels(
        self, block_rows: Optional[int] = None, depth: Optional[int] = None
    ) -> Iterator[jax.Array]:
        """`row_panels` with depth-deep prefetch — see module-level
        `prefetch_panels` (this method exists so duck-typed consumers like
        core/adaptive.py can reach the pipeline without importing it)."""
        return prefetch_panels(self, block_rows, depth)

    @property
    def T(self) -> "LinOp":
        """The transposed operator (matmat/rmatmat swapped)."""
        return _TransposedOp(self)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}(shape={self.shape}, dtype={jnp.dtype(self.dtype).name})"


class _TransposedOp(LinOp):
    def __init__(self, op: LinOp):
        self._op = op

    @property
    def shape(self):
        s = self._op.shape
        return s[:-2] + (s[-1], s[-2])

    @property
    def dtype(self):
        return self._op.dtype

    def matmat(self, X):
        return self._op.rmatmat(X)

    def rmatmat(self, Y):
        return self._op.matmat(Y)

    @property
    def T(self) -> LinOp:
        return self._op


class DenseOp(LinOp):
    """Device-resident 2-D array (the paper's in-core case)."""

    def __init__(self, array, block_rows: Optional[int] = None,
                 pipeline_depth: Optional[int] = None):
        if getattr(array, "ndim", None) != 2:
            raise ValueError(f"DenseOp expects a 2-D array, got shape {getattr(array, 'shape', None)}")
        self.array = array
        self.block_rows = block_rows
        self.pipeline_depth = pipeline_depth

    @property
    def shape(self):
        return tuple(self.array.shape)

    @property
    def dtype(self):
        return self.array.dtype

    def matmat(self, X):
        return self.array @ X

    def rmatmat(self, Y):
        return self.array.T @ Y

    def row_panels(self, block_rows: Optional[int] = None):
        m = self.shape[0]
        b = block_rows or self.block_rows or m
        device_resident = isinstance(self.array, jax.Array)
        for i, lo in enumerate(range(0, m, b)):
            panel = self.array[lo : min(lo + b, m)]
            # Device-resident arrays slice lazily — re-wrapping the slice in
            # jnp.asarray forced a per-panel copy of data that never left
            # HBM.  Host (numpy) slices keep the explicit host->device move
            # (the HostOp contract; prefetch_panels overlaps it).
            if not device_resident:
                with trace.span("pipeline.panel"):
                    panel = pipeline_mod.put_panel(panel, i)
            yield panel


class HostOp(DenseOp):
    """Host (numpy) 2-D array, possibly larger than device memory.

    Only one `block_rows x n` panel is device-resident at a time (the
    out-of-core contract of core/blocked.py); `matmat`/`rmatmat` stream the
    panels so even composed operators over a HostOp never move A wholesale.
    """

    DEFAULT_BLOCK_ROWS = 4096

    def __init__(self, array, block_rows: Optional[int] = None,
                 pipeline_depth: Optional[int] = None):
        array = np.asarray(array)
        super().__init__(array, block_rows or self.DEFAULT_BLOCK_ROWS,
                         pipeline_depth)

    def matmat(self, X):
        # prefetch_panels: panel p+1 transfers while panel p multiplies —
        # same values, same summation order as the synchronous walk.
        parts = [panel @ X for panel in self.prefetch_panels()]
        return jnp.concatenate(parts, axis=0)

    def rmatmat(self, Y):
        m, _ = self.shape
        out = None
        lo = 0
        for panel in self.prefetch_panels():
            hi = lo + panel.shape[0]
            contrib = panel.T @ Y[lo:hi]
            out = contrib if out is None else out + contrib
            lo = hi
        return out


class StackedOp(LinOp):
    """3-D batch [B, m, n]: a fleet of small SVDs under one vmap."""

    def __init__(self, array):
        if getattr(array, "ndim", None) != 3:
            raise ValueError(f"StackedOp expects [B, m, n], got shape {getattr(array, 'shape', None)}")
        self.array = jnp.asarray(array) if isinstance(array, np.ndarray) else array

    @property
    def shape(self):
        return tuple(self.array.shape)

    @property
    def dtype(self):
        return self.array.dtype

    def matmat(self, X):
        return self.array @ X      # batched matmul, X: [B, n, s] or [n, s]

    def rmatmat(self, Y):
        return jnp.swapaxes(self.array, -1, -2) @ Y


class ShardedOp(LinOp):
    """Row-sharded 2-D array on a device mesh (core/distributed.py path)."""

    def __init__(self, array, mesh: jax.sharding.Mesh, axis: str = "data"):
        if getattr(array, "ndim", None) != 2:
            raise ValueError(f"ShardedOp expects a 2-D array, got shape {getattr(array, 'shape', None)}")
        self.array = array
        self.mesh = mesh
        self.axis = axis
        self.sharding = (mesh, axis)

    @property
    def shape(self):
        return tuple(self.array.shape)

    @property
    def dtype(self):
        return self.array.dtype

    def matmat(self, X):
        return self.array @ X

    def rmatmat(self, Y):
        return self.array.T @ Y

    def row_panels(self, block_rows: Optional[int] = None):
        yield jnp.asarray(self.array)


class SparseOp(LinOp):
    """Sparse 2-D source (jax BCOO; scipy CSR/CSC/COO accepted).

    The recommender/graph/text workload class: the sketch Y = A @ Omega is an
    SpMM costing O(nnz * s) instead of O(m n s), so rSVD's dominant pass
    scales with the data that EXISTS.  `matmat`/`rmatmat` are BCOO SpMMs
    (A is never densified); `sketch` takes the fused path — a Pallas kernel
    (kernels/spmm_sketch.py) that streams block-ELL tiles of A and generates
    the matching Omega tiles in VMEM from the counter RNG, so Omega never
    touches HBM (on the CPU backend the kernel runs in Pallas interpret
    mode).  Structured sketch kinds, f64 sources, and matrices whose
    block-ELL padding would pass `MAX_PACK_FILL` take a materialized-Omega
    SpMM instead; a kernel the TPU compiler refuses raises, it is never
    swapped for that SpMM.

    `row_panels` inherits the basis-slice fallback — each panel is one
    nnz-proportional `rmatmat`, so panel walks (residuals, column means)
    stay sparse too; `block_rows` defaults bounded so those walks never
    materialize more than a panel of the dense form."""

    DEFAULT_BLOCK_ROWS = 4096

    #: fused-path guard: if block-ELL zero-padding would inflate the stored
    #: tiles past this fraction of the dense footprint, the structure is not
    #: sparse enough for the tiled kernel to win — use the BCOO SpMM.
    MAX_PACK_FILL = 0.5

    def __init__(self, a, block_rows: Optional[int] = None):
        if _scipy_sparse is not None and _scipy_sparse.issparse(a):
            a = jsparse.BCOO.from_scipy_sparse(a.tocoo())
        if isinstance(a, jsparse.JAXSparse) and not isinstance(a, jsparse.BCOO):
            to_bcoo = getattr(a, "to_bcoo", None)
            if to_bcoo is None:
                raise TypeError(
                    f"SparseOp cannot convert {type(a).__name__} to BCOO"
                )
            a = to_bcoo()
        if not isinstance(a, jsparse.BCOO):
            raise TypeError(
                "SparseOp expects a jax BCOO or a scipy sparse matrix, got "
                f"{type(a).__name__}"
            )
        if a.ndim != 2:
            raise ValueError(f"SparseOp expects a 2-D matrix, got shape {a.shape}")
        self.bcoo = a
        self.block_rows = block_rows or self.DEFAULT_BLOCK_ROWS
        self._t = None          # cached transposed BCOO for rmatmat
        self._packed = {}       # (bm, bk) -> block-ELL pack, or None if too dense

    @property
    def shape(self):
        return tuple(self.bcoo.shape)

    @property
    def dtype(self):
        return self.bcoo.dtype

    @property
    def nnz(self) -> int:
        """Stored nonzeros (the planner's traffic-model input)."""
        return int(self.bcoo.nse)

    @property
    def density(self) -> float:
        m, n = self.shape
        return self.nnz / float(m * n)

    def matmat(self, X):
        return self.bcoo @ X

    def rmatmat(self, Y):
        if self._t is None:
            self._t = self.bcoo.T
        return self._t @ Y

    def sketch(self, s: int, seed: int, kind: str = "gaussian") -> jax.Array:
        """Y = A @ Omega without materializing Omega in HBM when possible.

        The fused path packs A into block-ELL tiles once (cached per tile
        shape) and runs the Pallas SpMM-sketch kernel; structured kinds and
        matrices whose padded tiles would exceed `MAX_PACK_FILL` of the
        dense footprint fall back to `matmat` on a materialized Omega —
        same map, different summation order."""
        from repro.core import sketch as sketch_mod
        from repro.kernels import ops as kernel_ops

        m, n = self.shape
        omega_dtype = jnp.promote_types(self.dtype, jnp.float32)
        packed = None
        # kernel accumulates fp32 — f64 sources keep the materialized path
        if kind not in sketch_mod.STRUCTURED_KINDS and self.dtype != jnp.float64:
            packed = self._block_ell(kernel_ops.spmm_blocks(self.shape, s, self.dtype))
        if packed is None:
            return self.matmat(sketch_mod.sketch_matrix(n, s, seed, kind, omega_dtype))
        data, tilecols = packed
        return kernel_ops.spmm_sketch(data, tilecols, s, seed=seed, kind=kind, m=m)

    def _block_ell(self, blocks):
        bm, bk = blocks
        key = (bm, bk)
        if key not in self._packed:
            from repro.kernels import spmm_sketch as spmm_mod

            self._packed[key] = spmm_mod.pack_block_ell(
                self.bcoo, bm, bk, max_fill=self.MAX_PACK_FILL
            )
        return self._packed[key]


# ---------------------------------------------------------------------------
# Composed operators — the matrix is never materialized
# ---------------------------------------------------------------------------

class ComposedOp(LinOp):
    """Base for operators derived from another operator.

    Subclasses implement `_panel_map(panel, lo, hi)` — the per-panel form of
    the composition — and get `row_panels` for free; `prefetch_panels`
    recurses into the BASE, so the host->device transfer under a composed
    operator is the thing that overlaps, with the panel transform riding the
    already-prefetched device panel."""

    def __init__(self, base: LinOp):
        self.base = as_linop(base)
        if len(self.base.shape) != 2:
            raise ValueError(
                f"composed operators require a 2-D base, got shape {self.base.shape}"
                " (stacked sources: compose per slice, or use core.pca.batched_pca"
                " for per-channel PCA)"
            )
        self.block_rows = self.base.block_rows
        self.pipeline_depth = self.base.pipeline_depth  # like block_rows: the
        # base is what streams, so its prefetch preference rides along

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype

    def _panel_map(self, panel: jax.Array, lo: int, hi: int) -> jax.Array:
        """The composition applied to base rows [lo, hi) (device-resident)."""
        raise NotImplementedError

    def row_panels(self, block_rows: Optional[int] = None):
        lo = 0
        for panel in self.base.row_panels(block_rows):
            hi = lo + panel.shape[0]
            yield self._panel_map(panel, lo, hi)
            lo = hi


class ScaledOp(ComposedOp):
    """alpha * A."""

    def __init__(self, base: LinOp, alpha: float):
        super().__init__(base)
        self.alpha = alpha

    def matmat(self, X):
        return self.alpha * self.base.matmat(X)

    def rmatmat(self, Y):
        return self.alpha * self.base.rmatmat(Y)

    def _panel_map(self, panel, lo, hi):
        return (self.alpha * panel).astype(panel.dtype)


class CenteredOp(ComposedOp):
    """A - 1 muᵀ: the PCA operator.  mu defaults to A's column means,
    computed LAZILY with one panel-streamed pass (so shape-only planning
    over a ShapeDtypeStruct source never touches data) — the centered
    matrix itself is never formed (the m x n temporary the old `pca`
    materialized)."""

    def __init__(self, base: LinOp, mu: Optional[jax.Array] = None):
        super().__init__(base)
        self._mu = None if mu is None else jnp.asarray(mu)

    @property
    def mu(self) -> jax.Array:
        if self._mu is None:
            self._mu = column_means(self.base)
        return self._mu

    def matmat(self, X):
        correction = self.mu @ X                       # (s,)
        return self.base.matmat(X) - correction[None, :]

    def rmatmat(self, Y):
        colsum = jnp.sum(Y, axis=0)                    # (s,)
        return self.base.rmatmat(Y) - jnp.outer(self.mu, colsum)

    def _panel_map(self, panel, lo, hi):
        return (panel - self.mu[None, :]).astype(panel.dtype)


class LowRankUpdateOp(ComposedOp):
    """A + U Vᵀ with skinny U (m x r), V (n x r).

    Deflation — peeling off an already-computed leading subspace so the
    next solve targets the residual spectrum — is
    ``LowRankUpdateOp(op, -(U * S), Vt.T)``, i.e. A - U S Vᵀ as an operator.
    """

    def __init__(self, base: LinOp, U: jax.Array, V: jax.Array):
        super().__init__(base)
        m, n = self.base.shape
        if U.shape[0] != m or V.shape[0] != n or U.shape[1] != V.shape[1]:
            raise ValueError(
                f"update factors U {U.shape} / V {V.shape} do not match operator {self.base.shape}"
            )
        self.U = U
        self.V = V

    def matmat(self, X):
        return self.base.matmat(X) + self.U @ (self.V.T @ X)

    def rmatmat(self, Y):
        return self.base.rmatmat(Y) + self.V @ (self.U.T @ Y)

    def _panel_map(self, panel, lo, hi):
        return (panel + self.U[lo:hi] @ self.V.T).astype(panel.dtype)


def deflated(base: LinOp, U: jax.Array, S: jax.Array, Vt: jax.Array) -> LowRankUpdateOp:
    """A - U S Vᵀ as an operator (the deflation workload)."""
    return LowRankUpdateOp(base, -(U * S[None, :]), Vt.T)


def prefetch_panels(
    op, block_rows: Optional[int] = None, depth: Optional[int] = None
) -> Iterator[jax.Array]:
    """`op.row_panels(block_rows)` with depth-deep prefetch: the production
    of panel i+1 (host->device copy, lazy slice, composed transform) is
    issued while the consumer computes on panel i.

    Panel VALUES and order are identical to the synchronous walk — only
    transfer timing changes — so any row_panels consumer can switch over
    without a numerics diff (tests/test_pipeline.py pins bit-identity).

    Depth: explicit arg > the `pipeline.default_depth(...)` ambient scope
    (how an ExecutionPlan's `pipeline_depth` reaches nested walks) > the
    source's own `pipeline_depth` attribute > auto (DEFAULT_DEPTH for
    host-resident sources, 1 — plain iteration — otherwise).

    Routing: host numpy sources with plain-slice panels (HostOp) take the
    staged ring (`pipeline.stream_host_panels`: uniform zero-padded staging
    buffers, bounded at `depth` in flight); composed operators recurse into
    their BASE so the transfer underneath is what overlaps; everything else
    gets the generic `pipeline.lookahead` pull-ahead."""
    op = as_linop(op)
    b = block_rows or op.block_rows or op.shape[0]
    if isinstance(op, ComposedOp):
        def _mapped():
            lo = 0
            for panel in prefetch_panels(op.base, b, depth):
                hi = lo + panel.shape[0]
                yield op._panel_map(panel, lo, hi)
                lo = hi
        return _mapped()
    arr = getattr(op, "array", None)
    host = isinstance(arr, np.ndarray)
    d = pipeline_mod.resolve_depth(depth, host_resident=host,
                                   source_default=op.pipeline_depth)
    # the staged ring replicates DenseOp's plain-slice panels exactly; a
    # subclass with its own row_panels semantics must keep them
    if host and d > 1 and type(op).row_panels is DenseOp.row_panels:
        return pipeline_mod.stream_host_panels(
            arr, pipeline_mod.panel_bounds(op.shape[0], b), d
        )
    return pipeline_mod.lookahead(op.row_panels(b), d)


def column_means(op: LinOp) -> jax.Array:
    """muᵀ = 1ᵀA / m, accumulated one row panel at a time (bounded default
    panel height — the fp32 per-panel cast must stay panel-sized even for
    sources without a block_rows of their own).

    Accumulation runs in ``promote_types(panel.dtype, float32)`` — f32 at
    minimum, and f64 for an f64-under-x64 source, where the closing
    ``astype(op.dtype)`` is the identity (tests/test_adaptive.py pins that
    the promoted precision survives end-to-end for CenteredOp/pca)."""
    op = as_linop(op)
    m = op.shape[0]
    b = op.block_rows or HostOp.DEFAULT_BLOCK_ROWS
    total = None
    for panel in prefetch_panels(op, b):
        contrib = jnp.sum(panel.astype(jnp.promote_types(panel.dtype, jnp.float32)), axis=0)
        total = contrib if total is None else total + contrib
    return (total / m).astype(op.dtype)


def as_linop(a) -> LinOp:
    """Coerce an array (or LinOp) to an operator source.

    2-D device arrays -> DenseOp, 2-D host numpy -> HostOp (streamed),
    3-D -> StackedOp, sparse (jax BCOO / scipy) -> SparseOp.  Already-sharded
    arrays are NOT auto-detected — wrap them in ShardedOp(mesh, axis)
    explicitly (the mesh axis is a caller decision, not an array property
    the tracer can see)."""
    if isinstance(a, LinOp):
        return a
    # sparse first: a BCOO *has* ndim == 2, and falling through would wrap
    # it in DenseOp and densify on the first matmat
    if isinstance(a, jsparse.JAXSparse):
        return SparseOp(a)
    if _scipy_sparse is not None and _scipy_sparse.issparse(a):
        return SparseOp(a)
    ndim = getattr(a, "ndim", None)
    if ndim == 3:
        return StackedOp(a)
    if ndim == 2:
        if isinstance(a, np.ndarray):
            return HostOp(a)
        return DenseOp(a)
    if ndim is None:
        raise TypeError(
            f"cannot interpret {type(a).__name__} as a LinOp (no .ndim — pass"
            " an array or a LinOp source)"
        )
    raise ValueError(
        f"operator sources must be 2-D (matrix) or 3-D (stacked batch), got "
        f"ndim={ndim} with shape {getattr(a, 'shape', None)}"
    )
