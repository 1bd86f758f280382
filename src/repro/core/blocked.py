"""Blocked/streaming + batched randomized SVD (beyond-paper subsystem).

Two execution shapes the paper's single-GPU Algorithm 1 cannot serve:

1. **Panel streaming** (`blocked_randomized_svd`): A (m x n, tall) is consumed
   in row panels of `block_rows` — A may live in host memory (a numpy array)
   with only one panel on device at a time.  The trick is the same one that
   makes the *distributed* RSVD collective-cheap (core/distributed.py): every
   reduction in Algorithm 1 factors through a small accumulated state,

     sketch    Y_p = A_p @ Omega          per-panel GEMM, counter-RNG Omega
                                          (optionally itself streamed over
                                          column panels: Y_p += A_pj @ Omega_j
                                          via the panel-offset sketch kernel)
     CholeskyQR2  G = sum_p Y_p^T Y_p     s x s accumulator  -> R; Q_p = Y_p R^-1
     power     Z = sum_p A_p^T Q_p        n x s accumulator  -> orthonormalize
     project   B = sum_p Q_p^T A_p        s x n accumulator
     small SVD of B, U_p = Q_p @ U_b      per-panel GEMM

   where the panel sum plays the role of the all-reduce (`jax.lax.psum`) in
   the distributed path — both call the same `qr.cholesky_r_from_gram`.
   Device-resident working set: the m x n input A never is (one
   block_rows x n panel at a time), but the SKETCH-WIDTH panels Y/Q (m x s
   total) and the assembled U (m x k) are kept on device — an n/s (~20-50x)
   reduction vs. dense, not full independence from m.  Every per-panel op is
   local, so a caller needing true O(1)-in-m residency can spill Y_p/Q_p to
   host between passes; this implementation keeps them resident for speed.

2. **Batched** (`batched_randomized_svd`): a fleet of small SVDs [B, m, n]
   under one vmap — per-channel PCA, per-layer GaLore projection refresh,
   scan-stacked weight factorization (serve/lowrank.py).  Sketch seeds are
   decorrelated per slice through the counter RNG (seed + batch index), which
   is why `core.sketch` accepts traced seeds.

Dispatch now lives in the execution planner (`repro.linalg.plan`): HostOp /
`block_rows` plans execute `svd_streamed`, StackedOp (3-D) plans execute
`svd_batched`; the deprecated `core.rsvd.randomized_svd` shim routes here
through the same planner.  See DESIGN.md §"Blocked & batched execution" and
§"API: operators and plans".

Out-of-core transfers are OVERLAPPED: every pass over A's panels goes
through the prefetch pipeline (linalg/pipeline.py — the plan's
`pipeline_depth`, double-buffered by default for host numpy sources), and
the per-panel accumulator updates (Gram, Z, B, streamed-sketch Y) are
donated jitted steps so each accumulator occupies ONE device buffer for the
whole pass.  Neither changes a single arithmetic operation — results stay
bit-identical to the synchronous, undonated walk (DESIGN.md §Pipeline).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qr as qr_mod
from repro.core import sketch as sketch_mod
from repro.core.rsvd import RSVDConfig, _rsvd_body, _small_svd


def _panel_bounds(m: int, b: int) -> List[Tuple[int, int]]:
    """[(lo, hi), ...] covering [0, m) in strides of b (last panel ragged).
    One source of truth — linalg/pipeline.py — shared with the staging ring
    and the bench, so panel coverage can never desynchronize."""
    from repro.linalg.pipeline import panel_bounds  # lazy: core stays cycle-free

    return panel_bounds(m, b)


def _device(panel, idx: int = 0) -> jax.Array:
    """Move one host panel to the device through the pipeline's put (traced
    and counted there); arrays already on the device pass through."""
    if isinstance(panel, np.ndarray):
        from repro.linalg import pipeline as pipe  # lazy: core stays cycle-free

        return pipe.put_panel(panel, idx)
    return jnp.asarray(panel)


def _panel_stream(A, bounds, depth):
    """Factory of device-panel passes over A's row slices, prefetched.

    Each call starts one pass; with resolved depth > 1 the host->device copy
    of panel i+1 is issued while panel i computes (linalg/pipeline.py) —
    host numpy sources take the staged ring (its synchronous walk at depth
    1), device arrays the plain lazy-slice walk.  Values and order are
    bit-identical to the synchronous walk either way.

    ``start`` begins the pass at panel ordinal ``start`` (a resumed solve
    re-walks only the panels its restored cursor has not consumed; probe /
    hook ordinals restart at 0 for the shortened pass)."""
    from repro.linalg import pipeline as pipe  # lazy: core stays cycle-free

    host = isinstance(A, np.ndarray)
    d = pipe.resolve_depth(depth, host_resident=host)
    if host:
        return lambda start=0: pipe.stream_host_panels(A, bounds[start:], d)
    return lambda start=0: pipe.lookahead(
        (A[lo:hi] for lo, hi in bounds[start:]), d)


# ---------------------------------------------------------------------------
# Donated per-panel update steps: the accumulator carries (Gram, Z, B, the
# streamed-sketch Y) are rebound every panel — donate_argnums lets XLA write
# the update into the SAME HBM buffer instead of reallocating per panel
# (the launch/dryrun.py train/serve-step pattern; like there, donation stays
# OUT of shard_map bodies — donate_argnums + manual-axis shard_map trips the
# XLA:CPU "Invalid binary instruction opcode copy" crash, so the distributed
# path in core/distributed.py keeps its undonated psum form).
# tests/test_pipeline.py asserts the input/output aliasing on compiled HLO.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _add_donated(acc, x):
    """acc + x, with acc's buffer reused for the result."""
    return acc + x


@functools.partial(jax.jit, donate_argnums=(0,))
def _accum_xty(acc, X, Y):
    """acc + Xᵀ Y — the power-loop Z and projection-B panel updates."""
    return acc + X.T @ Y


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("backend",))
def _gram_accum(G, Yp, *, backend):
    """G + YpᵀYp through the named kernel backend (static: the backend is a
    trace-time switch, so it must key the jit cache — the ambient context
    at call time may differ from the one a cached trace was built under)."""
    with qr_mod.kernel_backend(backend):
        return G + qr_mod.gram(Yp)


def _accum_panels(terms):
    """Left-associated sum of an iterable of equally-shaped terms, donating
    the running accumulator (same order as functools.reduce(jnp.add, ...),
    so results are bit-identical to the undonated form)."""
    acc = None
    for t in terms:
        acc = t if acc is None else _add_donated(acc, t)
    return acc


# ---------------------------------------------------------------------------
# Streamed sketch: Y += A_panel @ Omega_panel, Omega never materialized whole
# ---------------------------------------------------------------------------

def streamed_sketch(
    A,
    s: int,
    seed: int,
    kind: sketch_mod.SketchKind = "gaussian",
    block_cols: int | None = None,
    fused: bool = False,
) -> jax.Array:
    """Y = A @ Omega(n, s; seed) accumulated over column panels of A.

    Panel j multiplies rows [j*b, (j+1)*b) of the *logical* Omega, regenerated
    in place from the counter RNG (`row_offset`), so at most one
    (block_cols x s) panel of Omega ever exists — and with ``fused`` not even
    that (the Pallas kernel generates Omega tiles in VMEM).  Bit-wise the
    panels are the monolithic Omega; only the fp32 summation order differs.
    """
    m, n = A.shape
    b = block_cols or n
    Y = jnp.zeros((m, s), jnp.float32)
    for j, (lo, hi) in enumerate(_panel_bounds(n, b)):
        panel = _device(A[:, lo:hi], j)
        if fused:
            from repro.kernels.ops import sketch_matmul

            Y = _add_donated(Y, sketch_matmul(
                panel, s, seed, kind=kind, out_dtype=jnp.float32, row_offset=lo
            ))
        else:
            omega = sketch_mod.sketch_matrix(
                hi - lo, s, seed, kind, dtype=jnp.float32, row_offset=lo
            )
            Y = _add_donated(Y, panel.astype(jnp.float32) @ omega)
    return Y.astype(jax.dtypes.canonicalize_dtype(A.dtype))


# ---------------------------------------------------------------------------
# Blocked CholeskyQR2 — the panel-sum twin of the distributed Gram all-reduce
# ---------------------------------------------------------------------------

def _blocked_gram(Y_panels: Sequence[jax.Array], G: jax.Array | None = None):
    """The panel-summed Gram G = Σ YpᵀYp (reusing a caller-supplied one)."""
    if G is not None:
        return G
    backend = qr_mod.active_kernel_backend()
    for Yp in Y_panels:
        G = qr_mod.gram(Yp) if G is None else _gram_accum(G, Yp, backend=backend)
    return G


def _blocked_cholesky_qr(Y_panels: Sequence[jax.Array], G: jax.Array | None = None,
                         shift=0.0, record_ortho: bool = False):
    """One CholeskyQR pass over a row-panel-split Y. Returns (Q_panels, R).

    The per-panel Gram and the R⁻¹ application go through the active kernel
    backend (qr.kernel_backend): "pallas" routes them to the SYRK and TRSM
    kernels, exactly as the dense and distributed paths do.  ``G`` lets the
    caller pass an already-reduced Gram (the sketch_gram epilogue) so the
    first pass skips re-reading every panel.  ``record_ortho`` feeds the
    accumulated Gram to the guard's orthogonality probe (set on a CQR2
    second pass, where G *is* ||Q1ᵀQ1 - I|| + I — a free byproduct)."""
    dtype = Y_panels[0].dtype
    G = _blocked_gram(Y_panels, G)
    # Factor and solve at >= fp32 (LAPACK has no bf16 Cholesky/TRSM), then
    # cast Q back so the panel dtype — and the assembled U — is preserved.
    fdtype = jnp.promote_types(dtype, jnp.float32)
    Gf = G.astype(fdtype)
    if record_ortho:
        qr_mod.record_ortho_gram(Gf)
    R = qr_mod.cholesky_r_from_gram(Gf, shift)
    Q_panels = [
        qr_mod.tri_solve_right(Yp.astype(fdtype), R).astype(dtype) for Yp in Y_panels
    ]
    return Q_panels, R


def _blocked_cholesky_qr2(Y_panels: Sequence[jax.Array], G1: jax.Array | None = None):
    """CholeskyQR2 on panels: O(eps) orthogonality for kappa(Y) <~ eps^-1/2,
    touching each panel twice and reducing only s x s Grams."""
    Q1, R1 = _blocked_cholesky_qr(Y_panels, G1)
    Q, R2 = _blocked_cholesky_qr(Q1, record_ortho=True)
    return Q, R2 @ R1


def _blocked_cholesky_qr3(Y_panels: Sequence[jax.Array], G1: jax.Array | None = None):
    """Shifted CholeskyQR3 on panels — the streamed twin of
    `qr.shifted_cholesky_qr3` (kappa(Y) up to ~1/eps), which the guard's
    retry ladder escalates to when a streamed CQR2 pass breaks down.

    The Fukaya et al. 2020 shift needs only ||Y||_F^2 = trace(G) — free
    from the Gram the first pass accumulates anyway, so the shifted pass
    still touches each panel exactly once."""
    m = sum(int(Yp.shape[0]) for Yp in Y_panels)
    G1 = _blocked_gram(Y_panels, G1)
    s = G1.shape[0]
    fdtype = jnp.promote_types(Y_panels[0].dtype, jnp.float32)
    eps = jnp.finfo(fdtype).eps
    shift = 11.0 * (m * s + s * (s + 1)) * eps * jnp.trace(G1.astype(fdtype))
    Q0, R0 = _blocked_cholesky_qr(Y_panels, G1, shift=shift)
    Q, R21 = _blocked_cholesky_qr2(Q0)
    return Q, R21 @ R0


# ---------------------------------------------------------------------------
# The orthonormalization steps between two passes over A, each one compiled
# program.  Run op by op, a step is some forty tiny dispatches (the Gram's
# floor shift, the Cholesky, the transposes, the TRSM and its casts, R2 R1),
# and the host's dispatch cost, not the arithmetic, sets its time.  Whatever
# the bodies read at trace time is a static argument: the CholeskyQR variant
# and the kernel backend (a thread-local, as for `_gram_accum`).  Guarded
# runs call the probed twins, which trace the same body under an open guard
# sink and return its probes as extra outputs (the `_batched_tall_probed`
# contract, `fault_key` included); the plain programs never carry a probe.
# ---------------------------------------------------------------------------

def _orth_panels_impl(Y_panels, G1, cqr3: bool, backend):
    """Panel CholeskyQR2 (shifted CQR3 when the plan, or the guard ladder
    via a replaced plan, asks for it) of a row-panel-split Y; returns
    (Q_panels, R).  Householder has no row-panel-split form: the ladder
    skips it for streamed plans and goes straight to the f64 recompute.

    Where a CQR2 Cholesky breaks down, the step returns the shifted CQR3
    of the same panels instead.  The floor shift leaves a Gram of kappa(Y)
    past eps^-1/2 with condition near 1/eps, and a TPU v5e's f32 Cholesky
    fails on a few such Grams (one solve in 1800 at 2000 x 2000, 1/i^2
    spectrum, k = 200).  A guarded run still records the breakdown."""
    with qr_mod.kernel_backend(backend):
        if cqr3:
            return _blocked_cholesky_qr3(Y_panels, G1)
        Q, R = _blocked_cholesky_qr2(Y_panels, G1)
        return jax.lax.cond(
            jnp.all(jnp.isfinite(R)), lambda: (Q, R),
            lambda: _unprobed(_blocked_cholesky_qr3, Y_panels, G1))


def _unprobed(fn, *args):
    """fn(*args) with an active guard sink hidden behind a throwaway one:
    a probe recorded inside a `lax.cond` branch may not leave it."""
    import sys

    g = sys.modules.get("repro.linalg.guard")
    if g is None or g.active_sink() is None:
        return fn(*args)
    with g.collecting():
        return fn(*args)


def _orthonormalize_impl(Z, method, backend):
    """orthonormalize(Z): the CholeskyQR2/3 of Z as a one-panel
    `_orth_panels_impl` (op for op `qr.orthonormalize`, with its breakdown
    fallback), other methods through `qr.orthonormalize`."""
    if method in ("cqr2", "cqr3"):
        return _orth_panels_impl([Z], None, method == "cqr3", backend)[0][0]
    with qr_mod.kernel_backend(backend):
        return qr_mod.orthonormalize(Z, method)


def _orth_trace_key(program: str, arrays, **static):
    """The `_note_trace` key of one orth program: its shapes, dtype and
    static arguments, so `trace_count` shows a new seed never retraces."""
    shapes = tuple(tuple(a.shape) for a in arrays if a is not None)
    return (f"engine.orth.{program}", shapes, jnp.dtype(arrays[0].dtype).name,
            tuple(sorted(static.items())))


@functools.partial(jax.jit, static_argnames=("cqr3", "backend"))
def _orth_panels(Y_panels, G1, *, cqr3: bool, backend):
    _note_trace(_orth_trace_key("panels", [*Y_panels, G1], g1=G1 is not None,
                                cqr3=cqr3, backend=backend))
    return _orth_panels_impl(Y_panels, G1, cqr3, backend)


@functools.partial(jax.jit, static_argnames=("method", "backend"))
def _orthonormalize(Z, *, method, backend):
    _note_trace(_orth_trace_key("orthonormalize", [Z], method=method,
                                backend=backend))
    return _orthonormalize_impl(Z, method, backend)


def _probed(impl):
    """The guarded twin of an orth program (see the section comment)."""

    def body(*args, fault_key, **static):
        del fault_key
        from repro.linalg import guard as guard_mod

        with guard_mod.collecting() as sink:
            out = impl(*args, **static)
        return out, sink.traced()

    return body


_orth_panels_probed = jax.jit(_probed(_orth_panels_impl),
                              static_argnames=("cqr3", "backend", "fault_key"))
_orthonormalize_probed = jax.jit(_probed(_orthonormalize_impl),
                                 static_argnames=("method", "backend",
                                                  "fault_key"))


def _orth_step(plain, probed, *args, **static):
    """Run one compiled orth step: the probed twin under an active guard
    sink (its probes folded into that sink), the plain program otherwise.
    Counts `engine.orth.calls`."""
    from repro.linalg import faults as faults_mod, guard as guard_mod, trace

    trace.add("engine.orth.calls")
    if guard_mod.active_sink() is not None:
        out, probes = probed(*args, fault_key=faults_mod.fingerprint(), **static)
        guard_mod.absorb(probes)
        return out
    return plain(*args, **static)


# ---------------------------------------------------------------------------
# Panel-streaming randomized SVD
# ---------------------------------------------------------------------------

def svd_streamed(
    A,
    k: int,
    cfg: RSVDConfig = RSVDConfig(),
    seed: int = 0,
    block_rows: int | None = None,
    pipeline_depth: int | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-k randomized SVD of A streamed in row panels of the tall side.

    Accepts a jax array OR a host numpy array (the out-of-core case: only
    `block_rows x n` of A is device-resident at a time; the s-column panels
    Y/Q — m x s in total — stay on device, see the module docstring).
    Host panels move through the prefetch pipeline: at `pipeline_depth`
    (arg > cfg.pipeline_depth > auto: double-buffered for numpy sources)
    panel i+1 transfers while panel i computes, every pass over A, with
    results bit-identical to the depth-1 synchronous walk.
    Returns (U, S, Vt) with the same contract as `linalg.svd`; U is
    assembled from per-panel GEMMs, so for a truly out-of-core caller the
    per-panel `Q_p @ U_b` products could be written back to host storage
    panel-by-panel instead.
    """
    m, n = A.shape
    if m < n:
        # Orientation swap: stream the taller side of A^T.  For numpy inputs
        # .T is a view — no host copy is made.
        V, S, Ut = svd_streamed(A.T, k, cfg, seed=seed, block_rows=block_rows,
                                pipeline_depth=pipeline_depth)
        return Ut.T, S, V.T

    b = block_rows or cfg.block_rows
    if not b:
        raise ValueError("svd_streamed needs block_rows (arg or cfg)")
    s = min(k + cfg.oversample, n)
    bounds = _panel_bounds(m, b)
    depth = pipeline_depth if pipeline_depth is not None else cfg.pipeline_depth
    panels = _panel_stream(A, bounds, depth)

    dtype = jax.dtypes.canonicalize_dtype(A.dtype)
    token = _stream_token(m, n, k, s, cfg, seed, dtype, nb=len(bounds))

    with qr_mod.kernel_backend(cfg.kernel_backend):
        return _blocked_body(panels, k, s, cfg, seed, dtype, token=token)


# ---------------------------------------------------------------------------
# The streamed solve as a resumable stage machine.  Stages walk A's panels
# with an explicit cursor and call `_stream_boundary` after each consumed
# panel, so linalg/snapshot.py can capture the accumulated state (Y panels,
# Gram, Z/B accumulators, cursor) at any panel-group boundary and a restored
# run continues the walk from `bounds[cursor:]`.  Everything NOT saved (the
# CholeskyQR bases Q/Qz, Omega slabs) is recomputed on restore from saved
# bytes through the same programs — resumed factors are bit-identical to the
# uninterrupted run.  With no snapshot scope active each boundary is one
# sys.modules probe; the arithmetic and its order are EXACTLY the
# pre-machine body's (tests/test_blocked.py pins fixed-seed bytes).
# ---------------------------------------------------------------------------

class _StreamState:
    """Mutable stage-machine state of one streamed solve.

    ``stage`` walks sketch -> (power_z -> power_y) x power_iters -> project;
    ``cursor`` counts panels consumed in the CURRENT pass, ``ticks`` counts
    boundaries ever crossed (monotonic across restarts — the snapshot step
    key), ``piter`` the current power iteration.  ``Y`` holds the current
    pass's basis panels (the NEW panels while power_y rebuilds them)."""

    __slots__ = ("stage", "piter", "cursor", "ticks", "Y", "G1", "Z", "B",
                 "token")

    def __init__(self, token: str):
        self.stage = "sketch"
        self.piter = 0
        self.cursor = 0
        self.ticks = 0
        self.Y = []
        self.G1 = None
        self.Z = None
        self.B = None
        self.token = token

    def capture(self):
        """(arrays, meta) for snapshot.Checkpointer — exact host bytes."""
        arrays = {f"Y{i:04d}": np.asarray(y) for i, y in enumerate(self.Y)}
        for name in ("G1", "Z", "B"):
            v = getattr(self, name)
            if v is not None:
                arrays[name] = np.asarray(v)
        meta = {"token": self.token, "engine": "streamed", "stage": self.stage,
                "piter": self.piter, "cursor": self.cursor,
                "ticks": self.ticks, "n_y": len(self.Y)}
        return arrays, meta

    @classmethod
    def restore(cls, snap, token: str) -> "_StreamState":
        _ref, arrays, meta = snap
        st = cls(token)
        st.stage = meta["stage"]
        st.piter = int(meta["piter"])
        st.cursor = int(meta["cursor"])
        st.ticks = int(meta["ticks"])
        st.Y = [jnp.asarray(arrays[f"Y{i:04d}"]) for i in range(meta["n_y"])]
        for name in ("G1", "Z", "B"):
            if name in arrays:
                setattr(st, name, jnp.asarray(arrays[name]))
        return st


def _stream_token(m: int, n: int, k: int, s: int, cfg: RSVDConfig, seed,
                  dtype, nb: int) -> str:
    """Fingerprint of everything the streamed numerics depend on: a snapshot
    resumes only a solve that would replay the identical op sequence."""
    return "|".join(str(x) for x in (
        "streamed", m, n, k, s, int(seed), jnp.dtype(dtype).name, nb,
        cfg.power_iters, cfg.power_scheme, cfg.qr_method, cfg.sketch_kind,
        bool(cfg.fused_sketch), cfg.block_cols, cfg.kernel_backend,
        cfg.small_svd, cfg.oversample))


def _stream_boundary(st: _StreamState) -> None:
    """Advance one panel and cross a snapshot boundary.  sys.modules probe:
    core stays import-cycle-free (the `_record_step_finite` pattern); the
    snapshot module is in sys.modules whenever repro.linalg is."""
    import sys

    st.cursor += 1
    st.ticks += 1
    snap = sys.modules.get("repro.linalg.snapshot")
    if snap is not None:
        snap.boundary(st.ticks, st.capture)


def _stream_resume(token: str) -> "_StreamState | None":
    import sys

    snap = sys.modules.get("repro.linalg.snapshot")
    if snap is None:
        return None
    found = snap.resume(token)
    return None if found is None else _StreamState.restore(found, token)


def _blocked_body(panels, k: int, s: int, cfg: RSVDConfig, seed, dtype,
                  token: "str | None" = None):
    """Steps 1-6 over the panel generator, under the active kernel backend.

    ``token`` (from `_stream_token`) enables snapshot/resume; None (direct
    callers, tests of the raw body) runs the fresh stage machine with
    boundaries still crossed — identical arithmetic either way.  Each pass
    over A and each step between passes runs under its `engine.*` span
    (linalg/trace.py)."""
    from repro.linalg.trace import span  # lazy: core stays cycle-free

    st = (_stream_resume(token) if token is not None else None) \
        or _StreamState(token or "")
    backend = qr_mod.active_kernel_backend()

    def panel_orth(Y, G1):
        return _orth_step(_orth_panels, _orth_panels_probed, Y, G1,
                          cqr3=cfg.qr_method == "cqr3", backend=backend)

    # Step 1-2a: per-panel sketch.  Omega is n x s regenerated per panel from
    # the counter RNG — identical for every panel, no broadcast state.  The
    # fused whole-panel sketch rides the Gram epilogue: each panel's
    # contribution to G = YᵀY is accumulated while Y_p is produced, so the
    # first CQR2 pass below never re-reads Y.  (Column-paneled sketches
    # accumulate Y_p across block_cols calls, so no per-call Gram exists;
    # f64 — the faithful enable_x64 setting — stays on the jnp sketch, like
    # the dense path's guard.)
    if st.stage == "sketch":
        with span("engine.sketch"):
            if cfg.fused_sketch and not cfg.block_cols and dtype != jnp.float64:
                from repro.kernels.ops import sketch_gram

                for Ap in panels(st.cursor):
                    y, g = sketch_gram(Ap, s, seed, kind=cfg.sketch_kind)
                    st.Y.append(y)
                    st.G1 = g if st.G1 is None else _add_donated(st.G1, g)
                    _stream_boundary(st)
            else:
                for Ap in panels(st.cursor):
                    st.Y.append(streamed_sketch(
                        Ap, s, seed, cfg.sketch_kind,
                        block_cols=cfg.block_cols,
                        fused=cfg.fused_sketch and dtype != jnp.float64,
                    ))
                    _stream_boundary(st)
        st.stage = "power_z" if cfg.power_iters else "project"
        st.cursor = 0

    # Step 2: power iteration through the n x s accumulator Z.  The Z / B
    # accumulators below are donated per panel (_accum_xty): one n x s (or
    # s x n) HBM buffer carries the whole pass instead of a fresh
    # allocation per panel, and the summation order is unchanged.
    while st.stage in ("power_z", "power_y"):
        if st.stage == "power_z":
            if cfg.power_scheme == "plain":
                src = st.Y
            else:
                # recomputed (not snapshotted) on resume: a deterministic
                # function of the saved Y panels + Gram, same program
                with span("engine.orth"):
                    src, _ = panel_orth(st.Y, st.G1)
            with span("engine.power_z"):
                for Ap, Xp in zip(panels(st.cursor), src[st.cursor:]):
                    st.Z = Ap.T @ Xp if st.Z is None else _accum_xty(st.Z, Ap, Xp)
                    _stream_boundary(st)
            st.stage, st.cursor, st.Y = "power_y", 0, []
        else:  # power_y: rebuild Y from the completed Z accumulator
            if cfg.power_scheme == "plain":
                mult = st.Z
            else:
                with span("engine.orth"):  # n x s, fits
                    mult = _orth_step(_orthonormalize, _orthonormalize_probed,
                                      st.Z, method=cfg.qr_method,
                                      backend=backend)
            with span("engine.power_y"):
                for Ap in panels(st.cursor):
                    st.Y.append(Ap @ mult)
                    _stream_boundary(st)
            st.G1 = None  # Y was replaced; the sketch-pass Gram is stale
            st.Z = None
            st.piter += 1
            st.stage = "power_z" if st.piter < cfg.power_iters else "project"
            st.cursor = 0

    # Steps 3-4: orthonormal range basis (panel-split; recomputed from the
    # saved Y/G1 on resume), then B = Q^T A through the s x n accumulator.
    with span("engine.orth"):
        Q, _ = panel_orth(st.Y, st.G1)
    with span("engine.project"):
        for Ap, Qp in zip(panels(st.cursor), Q[st.cursor:]):
            st.B = Qp.T @ Ap if st.B is None else _accum_xty(st.B, Qp, Ap)
            _stream_boundary(st)

    # Steps 5-6: small SVD (s x n, in-memory) and per-panel U assembly.
    with span("engine.small_svd"):
        U_b, S, Vt = _small_svd(st.B, cfg.small_svd)
    with span("engine.assemble"):
        U = jnp.concatenate([Qp @ U_b[:, :k] for Qp in Q], axis=0)
        return U, S[:k], Vt[:k, :]


def eigvals_streamed(
    A, k: int, cfg: RSVDConfig = RSVDConfig(), seed: int = 0,
    block_rows: int | None = None, pipeline_depth: int | None = None,
) -> jax.Array:
    """k largest singular values, streaming — Sigma-only mode of the above."""
    _, S, _ = svd_streamed(A, k, cfg, seed=seed, block_rows=block_rows,
                           pipeline_depth=pipeline_depth)
    return S


# ---------------------------------------------------------------------------
# Batched (vmap) randomized SVD
# ---------------------------------------------------------------------------

# Trace-time tally: how many times the batched body was TRACED (not
# executed) per (shape, dtype, k, cfg).  The count is taken inside the
# function body, so it ticks at trace time only and a jit cache hit leaves it
# untouched — the serve-layer executable cache asserts steady-state
# re-trace-freedom (at most one trace per distinct plan) against it.  It
# lives in linalg/trace.py's thread-safe counters as `jit.traces.<key>`.

def _trace_key(shape, dtype, k: int, cfg: RSVDConfig):
    return (tuple(shape), jnp.dtype(dtype).name, int(k), cfg)


def _trace_counter(key) -> str:
    """The counter that tallies traces of a `_trace_key`."""
    return f"jit.traces.{key!r}"


def _note_trace(key) -> None:
    from repro.linalg import trace  # lazy: core stays cycle-free

    trace.add(_trace_counter(key))


def trace_count(key) -> int:
    """Exact number of traces recorded for a `_trace_key` (thread-safe)."""
    from repro.linalg import trace

    return trace.count(_trace_counter(key))


def _batched_tall_body(A: jax.Array, seeds: jax.Array, k: int, cfg: RSVDConfig):
    _note_trace(_trace_key(A.shape, A.dtype, k, cfg))
    with qr_mod.kernel_backend(cfg.kernel_backend):
        return jax.vmap(lambda a, sd: _rsvd_body(a, k, cfg, sd))(A, seeds)


_batched_tall = jax.jit(_batched_tall_body, static_argnames=("k", "cfg"))


@functools.partial(jax.jit, static_argnames=("k", "cfg", "fault_key"))
def _batched_tall_probed(A: jax.Array, seeds: jax.Array, k: int,
                         cfg: RSVDConfig, fault_key=()):
    """Guarded twin of `_batched_tall`: same body traced under an open
    guard sink; the per-slice probe scalars come back batched as extra jit
    outputs and the driver max/any-reduces them (guard.absorb).  See
    rsvd._randomized_svd_dense_probed for the fault_key cache contract."""
    del fault_key
    from repro.linalg import guard as guard_mod

    def one(a, sd):
        with guard_mod.collecting() as sink:
            out = _rsvd_body(a, k, cfg, sd)
        return out, sink.traced()

    with qr_mod.kernel_backend(cfg.kernel_backend):
        return jax.vmap(one)(A, seeds)


def batched_cfg(cfg: RSVDConfig) -> RSVDConfig:
    """The config the batched body actually traces with: fused power and the
    streaming fields are normalized away (meaningless under vmap — they
    would only fragment the jit cache key).  The serve-layer executable
    cache applies the SAME normalization when predicting a plan's trace
    key, so cache bookkeeping and execution can never drift apart."""
    if cfg.fused_power or cfg.block_rows or cfg.pipeline_depth:
        return dataclasses.replace(cfg, fused_power=False, block_rows=None,
                                   pipeline_depth=None)
    return cfg


def slice_seeds(seed, B: int) -> jax.Array:
    """Per-slice sketch seeds for a [B, m, n] batch.

    A scalar keeps the historical contract — slice i sketches with
    seed + i, a disjoint logical stream of the counter RNG.  A (B,)-shaped
    array pins each slice's seed EXPLICITLY: the request-coalescing service
    stacks unrelated requests into one batch, so slice seeds must follow
    the requests they came from (permuting arrival order permutes seeds
    with the slices, leaving every per-request result bit-identical)."""
    if np.ndim(seed) == 0:
        return jnp.uint32(seed) + jnp.arange(B, dtype=jnp.uint32)
    seeds = jnp.asarray(seed, jnp.uint32)
    if seeds.shape != (B,):
        raise ValueError(
            f"per-slice seeds must have shape ({B},) to match the batch, "
            f"got {tuple(seeds.shape)}")
    return seeds


def svd_batched(
    A: jax.Array,
    k: int,
    cfg: RSVDConfig = RSVDConfig(),
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-k randomized SVD of every slice of A: [B, m, n] -> (U [B, m, k],
    S [B, k], Vt [B, k, n]).

    One vmapped program instead of B kernel launches — the fleet-of-small-
    matrices workload (per-channel PCA, per-layer gradient compression).
    Slice i sketches with seed + i (or with ``seed[i]`` when ``seed`` is a
    (B,)-shaped array — see `slice_seeds`): the counter RNG makes that a
    disjoint logical stream, so batching changes nothing statistically vs.
    a Python loop with per-matrix seeds.

    The fused-sketch kernel takes its seed as a traced SMEM scalar, so the
    per-slice seeds vmap straight through it — the batched path uses the
    in-VMEM Omega generation like the dense path does.  The fused POWER
    path is disabled under vmap (its n x s VMEM accumulators would be
    per-slice); at batched (small-matrix) sizes power GEMMs are cheap.
    """
    if A.ndim != 3:
        raise ValueError(f"batched path expects [B, m, n], got shape {A.shape}")
    _, m, n = A.shape
    if m < n:
        V, S, Ut = svd_batched(jnp.swapaxes(A, -1, -2), k, cfg, seed=seed)
        return jnp.swapaxes(Ut, -1, -2), S, jnp.swapaxes(V, -1, -2)
    cfg = batched_cfg(cfg)
    seeds = slice_seeds(seed, A.shape[0])
    from repro.linalg import faults as faults_mod, guard as guard_mod

    if guard_mod.active_sink() is not None:
        out, probes = _batched_tall_probed(A, seeds, k, cfg,
                                           faults_mod.fingerprint())
        guard_mod.absorb(probes)
        return out
    return _batched_tall(A, seeds, k, cfg)


# Pre-facade names, kept importable for downstream code; the repo itself
# calls the new names (or, preferably, `repro.linalg.svd`).
blocked_randomized_svd = svd_streamed
blocked_randomized_eigvals = eigvals_streamed
batched_randomized_svd = svd_batched
