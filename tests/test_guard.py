"""Guarded execution: probes, the escalation ladder, and the validate= knob.

The contract under test (DESIGN.md §Guarded execution):

  * guard off is BIT-identical to a pre-guard solve at fixed seed — the
    probe call sites never run without an active sink, and the probed jit
    twins are separate cache entries;
  * report mode observes from byproducts only: factors stay bit-identical,
    the plan's predicted HBM traffic is unchanged (no extra pass over A),
    and a HealthReport rides on the Decomposition;
  * retry mode climbs cqr2 -> cqr3 -> householder -> f64+reseed (streamed
    plans skip householder) until the explicitly verified ||QtQ - I||_F
    meets the policy's ortho tolerance, recording every rung;
  * validate= screens the input for non-finite values, naming the offending
    panel on streamed sources, and is a bit-identical passthrough on clean
    input.

Rung pins are EMPIRICAL (this backend, these shapes): dense f32 stays on
cqr2 through kappa=1e6 and escalates once at 1e8; the f64 planner already
plans householder (single healthy rung); adaptive runs land on householder
under the default f32 tolerance because panel-accumulated CGS2 leaves
||QtQ - I||_F at a few 1e-5 — a relaxed ortho_tol pins them to cqr2.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro import linalg
from repro.compat import enable_x64
from repro.linalg import faults, guard


def _ill_np(m, n, kappa, seed=0):
    """Dense matrix with exactly log-spaced spectrum 1 .. 1/kappa (f64
    construction, cast by the caller)."""
    rng = np.random.default_rng(seed)
    U, _ = np.linalg.qr(rng.standard_normal((m, min(m, n))))
    V, _ = np.linalg.qr(rng.standard_normal((n, min(m, n))))
    s = np.logspace(0.0, -np.log10(kappa), min(m, n))
    return (U * s) @ V.T


@functools.lru_cache(maxsize=None)
def _ill_f32(m, n, kappa, seed=0):
    return np.asarray(_ill_np(m, n, kappa, seed), dtype=np.float32)


@functools.lru_cache(maxsize=None)
def _gauss(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((m, n)).astype(np.float32)


def _same(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# policy plumbing


class TestPolicy:
    def test_mode_validated(self):
        with pytest.raises(ValueError, match="guard mode"):
            guard.GuardPolicy(mode="paranoid")
        with pytest.raises(ValueError, match="max_retries"):
            guard.GuardPolicy(max_retries=-1)

    def test_as_guard_coercions(self):
        assert guard.as_guard(None).mode == "off"
        assert guard.as_guard("retry").mode == "retry"
        p = guard.GuardPolicy(mode="report")
        assert guard.as_guard(p) is p
        with pytest.raises(TypeError):
            guard.as_guard(42)

    def test_ortho_tol_defaults(self):
        p = guard.GuardPolicy(mode="retry")
        assert p.resolve_ortho_tol("float32") == pytest.approx(1e-5)
        assert p.resolve_ortho_tol("float64") == pytest.approx(1e-10)
        assert guard.GuardPolicy(ortho_tol=3e-4).resolve_ortho_tol(
            "float64") == pytest.approx(3e-4)

    def test_hashable_for_static_jit_args(self):
        # GuardPolicy rides on the frozen ExecutionPlan, which jitted
        # consumers (core/pca.py) take as a static argument
        assert hash(guard.GuardPolicy()) == hash(guard.GuardPolicy())
        assert guard.GuardPolicy() != guard.GuardPolicy(mode="retry")

    def test_escalation_methods(self):
        A = _gauss(96, 64)
        pl = linalg.plan(jnp.asarray(A), 8)
        assert guard._escalation_methods(pl) == ["cqr3", "householder"]
        pls = linalg.plan(linalg.HostOp(A, block_rows=32), 8)
        assert pls.path == "streamed"
        assert guard._escalation_methods(pls) == ["cqr3"]


class TestDescribe:
    def test_default_plan_has_no_guard_bits(self):
        d = linalg.plan(jnp.asarray(_gauss(96, 64)), 8).describe()
        assert "guard" not in d and "validate" not in d

    def test_non_default_bits_printed(self):
        d = linalg.plan(jnp.asarray(_gauss(96, 64)), 8,
                        guard="retry", validate=True).describe()
        assert "guard=retry" in d and "validate=on" in d


# ---------------------------------------------------------------------------
# report mode: zero-extra-read observation


class TestReportMode:
    def test_dense_bit_identical_with_health(self):
        A = jnp.asarray(_gauss(96, 64))
        base = linalg.svd(A, 8, seed=3)
        d = linalg.decompose(A, 8, seed=3, guard="report")
        _same(base, d.factors)
        assert d.health is not None and d.health.ok
        assert d.health.mode == "report"
        assert len(d.health.attempts) == 1
        assert d.health.final.first_pass_ortho is not None
        assert d.health.final.cond_proxy is not None

    def test_streamed_bit_identical_with_health(self):
        A = _gauss(256, 64, seed=1)
        base = linalg.svd(linalg.HostOp(A, block_rows=64), 8, seed=7)
        d = linalg.decompose(linalg.HostOp(A, block_rows=64), 8, seed=7,
                             guard="report")
        _same(base, d.factors)
        assert d.health.ok and d.health.rung_used == "cqr2"

    def test_predicted_hbm_unchanged(self):
        # the acceptance roofline assert: report probes add no reads of A
        A = jnp.asarray(_gauss(96, 64))
        off = linalg.plan(A, 8)
        rep = linalg.plan(A, 8, guard="report")
        assert rep.predicted_hbm_bytes == off.predicted_hbm_bytes

    def test_guard_off_no_health(self):
        d = linalg.decompose(jnp.asarray(_gauss(96, 64)), 8, seed=3)
        assert d.health is None

    def test_batched_source_reports(self):
        W = jnp.asarray(np.stack([_gauss(96, 64, seed=s) for s in range(3)]))
        base = linalg.svd(linalg.StackedOp(W), 8, seed=2)
        d = linalg.decompose(linalg.StackedOp(W), 8, seed=2, guard="report")
        _same(base, d.factors)
        assert d.health.ok
        assert d.health.final.first_pass_ortho is not None

    def test_report_does_not_escalate(self):
        A = jnp.asarray(_ill_f32(96, 64, 1e8))
        with faults.inject("cholesky_breakdown"):
            d = linalg.decompose(A, 8, seed=5, guard="report")
        assert not d.health.ok
        assert len(d.health.attempts) == 1
        assert d.health.final.breakdown

    def test_streamed_breakdown_flagged(self):
        # the fault fires inside the streamed engine's compiled orth step,
        # traced under the probed twin's sink and absorbed into the report
        op = linalg.HostOp(_gauss(224, 48, seed=4), block_rows=64)
        with faults.inject("cholesky_breakdown"):
            d = linalg.decompose(op, 8, seed=5, guard="report")
        assert not d.health.ok
        assert len(d.health.attempts) == 1
        assert d.health.final.breakdown


# ---------------------------------------------------------------------------
# retry mode: the escalation ladder (empirical rung pins)


class TestRetryLadder:
    @pytest.mark.parametrize("kappa,rungs", [
        (1e2, ("cqr2",)),
        (1e4, ("cqr2",)),
        (1e6, ("cqr2",)),
        (1e8, ("cqr2",)),
    ])
    def test_dense_f32_sweep(self, kappa, rungs):
        # at sketch width s=18 the top of a log-spaced spectrum spans only
        # ~kappa^(17/63), so kappa(Y) never crosses the CQR2 edge here and
        # every rung verifies on cqr2 — escalation under natural (unfaulted)
        # conditions is exercised by the adaptive tests below, and under
        # breakdown by TestAcceptance
        A = jnp.asarray(_ill_f32(96, 64, kappa))
        d = linalg.decompose(A, 8, seed=5, guard="retry")
        h = d.health
        assert h.ok
        assert tuple(a.rung for a in h.attempts) == rungs
        assert h.rung_used == rungs[-1]
        assert h.final.ortho_fro is not None and h.final.ortho_fro <= 1e-5

    def test_probe_fires_past_cqr2_edge(self):
        # a tighter probe_tol turns the edge-of-validity warning (probe
        # ~0.1 at kappa(Y) ~ eps^{-1/2}) into an escalation; the stronger
        # rung must then clear it
        A = jnp.asarray(_ill_f32(96, 64, 1e8))
        d = linalg.decompose(
            A, 8, seed=5, guard=linalg.GuardPolicy(mode="retry", probe_tol=0.01))
        h = d.health
        assert h.ok and h.rung_used == "cqr3"
        assert h.attempts[0].first_pass_ortho > 0.01
        assert h.attempts[1].first_pass_ortho <= 0.01

    @pytest.mark.parametrize("kappa", [1e2, 1e8])
    def test_streamed_f32_sweep(self, kappa):
        A = np.asarray(_ill_np(256, 64, kappa), dtype=np.float32)
        op = linalg.HostOp(A, block_rows=64, pipeline_depth=2)
        d = linalg.decompose(op, 8, seed=5, guard="retry")
        assert d.health.ok and d.health.rung_used == "cqr2"
        assert d.health.final.ortho_fro <= 1e-5

    @pytest.mark.parametrize("kappa", [1e2, 1e8])
    def test_adaptive_default_lands_on_householder(self, kappa):
        # panel-accumulated CGS2 leaves ||QtQ - I||_F at a few 1e-5 under
        # cqr2/cqr3, above the default f32 tolerance — the ladder tops out
        A = jnp.asarray(_ill_f32(96, 64, kappa))
        d = linalg.decompose(A, linalg.Tolerance(5e-2), seed=3, guard="retry")
        h = d.health
        assert h.ok and h.rung_used == "householder"
        assert tuple(a.rung for a in h.attempts) == (
            "cqr2", "cqr3", "householder")
        assert h.final.ortho_fro <= 1e-5

    def test_adaptive_relaxed_tol_stays_on_cqr2(self):
        A = jnp.asarray(_ill_f32(96, 64, 1e2))
        d = linalg.decompose(
            A, linalg.Tolerance(5e-2), seed=3,
            guard=linalg.GuardPolicy(mode="retry", ortho_tol=1e-3))
        assert d.health.ok
        assert tuple(a.rung for a in d.health.attempts) == ("cqr2",)

    @pytest.mark.parametrize("kappa", [1e4, 1e8])
    def test_dense_f64_planned_householder(self, kappa):
        # the planner already plans householder for f64 dense sources — the
        # first rung is healthy at the f64 tolerance, no escalation
        with enable_x64():
            A = jnp.asarray(_ill_np(96, 64, kappa))
            assert A.dtype == jnp.float64
            d = linalg.decompose(A, 8, seed=5, guard="retry")
        h = d.health
        assert h.ok and tuple(a.rung for a in h.attempts) == ("householder",)
        assert h.final.ortho_fro <= 1e-10

    def test_max_retries_bounds_the_ladder(self):
        A = jnp.asarray(_ill_f32(96, 64, 1e8))
        with faults.inject("cholesky_breakdown"):  # every cholesky rung dies
            d = linalg.decompose(
                A, 8, seed=5,
                guard=linalg.GuardPolicy(mode="retry", max_retries=1))
        assert not d.health.ok
        assert len(d.health.attempts) == 2  # first attempt + one escalation

    def test_streamed_breakdown_climbs_to_cqr3(self):
        # one forced breakdown (the first compiled orth step of the cqr2
        # rung); the probed cqr3 program then runs clean and verifies
        op = linalg.HostOp(_gauss(224, 48, seed=6), block_rows=64)
        with faults.inject("cholesky_breakdown", times=1):
            d = linalg.decompose(op, 8, seed=5, guard="retry")
        h = d.health
        assert h.ok and h.rung_used == "cqr3"
        assert tuple(a.rung for a in h.attempts) == ("cqr2", "cqr3")
        assert h.attempts[0].breakdown and not h.final.breakdown
        assert h.final.ortho_fro <= 1e-5

    def test_ladder_exhausted_returns_last_flagged(self):
        A = _gauss(256, 64, seed=1)
        op = linalg.HostOp(A, block_rows=64, pipeline_depth=2)
        with faults.inject("cholesky_breakdown"):  # no householder rung to
            d = linalg.decompose(op, 8, seed=7, guard="retry")  # hide in
        assert not d.health.ok
        assert d.health.attempts[-1].breakdown

    def test_guarded_qb_eigh_pca_verify(self):
        A = _gauss(96, 64)
        for kind, src in (("qb", jnp.asarray(A)),
                          ("eigh", jnp.asarray(A.T @ A)),
                          ("pca", jnp.asarray(A))):
            d = linalg.decompose(src, 8, kind=kind, seed=2, guard="retry")
            assert d.health.ok, kind
            assert d.health.final.ortho_fro is not None, kind

    def test_guarded_lu_skips_verification(self):
        # lu has no orthonormal factor — probes still gate, verification is
        # skipped rather than failing on a triangular factor
        d = linalg.decompose(jnp.asarray(_gauss(96, 64)), 8, kind="lu",
                             seed=2, guard="retry")
        assert d.health.ok
        assert d.health.final.ortho_fro is None


class TestAcceptance:
    def test_breakdown_recovers_via_ladder(self):
        """The PR's acceptance scenario: an injected f32 Cholesky breakdown
        at kappa=1e8 forces the retry ladder through cqr2 and cqr3 (both
        poisoned) to householder, which recovers to a verified
        ||QtQ - I||_F <= 1e-5, and the report names the rung."""
        A = jnp.asarray(_ill_f32(96, 64, 1e8))
        with faults.inject("cholesky_breakdown"):
            d = linalg.decompose(A, 8, seed=5, guard="retry")
        h = d.health
        assert h.ok
        assert h.rung_used == "householder"
        assert tuple(a.rung for a in h.attempts) == (
            "cqr2", "cqr3", "householder")
        assert all(a.breakdown for a in h.attempts[:2])
        assert h.final.ortho_fro <= 1e-5
        assert "rung_used=householder" in h.describe()
        U, S, Vt = d.factors
        assert bool(jnp.isfinite(S).all())


# ---------------------------------------------------------------------------
# validate=


class TestValidate:
    def test_clean_passthrough_bit_identical(self):
        A = jnp.asarray(_gauss(96, 64))
        _same(linalg.svd(A, 8, seed=3), linalg.svd(A, 8, seed=3, validate=True))

    def test_dense_device_source_screened(self):
        A = np.array(_gauss(96, 64))
        A[10, 3] = np.inf
        with pytest.raises(ValueError, match="validate: non-finite"):
            linalg.svd(jnp.asarray(A), 8, seed=3, validate=True)

    def test_streamed_names_the_panel(self):
        A = np.array(_gauss(256, 64, seed=1))
        A[70, 3] = np.nan  # rows 64:128 -> panel 1 at block_rows=64
        op = linalg.HostOp(A, block_rows=64, pipeline_depth=2)
        with pytest.raises(ValueError, match=r"panel 1 \(rows 64:128\)"):
            linalg.svd(op, 8, seed=7, validate=True)

    def test_streamed_clean_bit_identical(self):
        A = _gauss(256, 64, seed=1)
        base = linalg.svd(linalg.HostOp(A, block_rows=64), 8, seed=7)
        val = linalg.svd(linalg.HostOp(A, block_rows=64), 8, seed=7,
                         validate=True)
        _same(base, val)

    def test_sparse_stored_values_screened(self):
        sp = pytest.importorskip("scipy.sparse")
        M = sp.random(96, 64, density=0.05, random_state=0, dtype=np.float32)
        M.data[0] = np.nan
        with pytest.raises(ValueError, match="sparse"):
            linalg.svd(linalg.SparseOp(M), 8, seed=3, validate=True)

    def test_validate_on_decompose_and_plan(self):
        A = np.array(_gauss(96, 64))
        A[0, 0] = np.nan
        pl = linalg.plan(jnp.asarray(A), 8, validate=True)
        assert pl.validate
        with pytest.raises(ValueError, match="validate"):
            linalg.decompose(jnp.asarray(A), 8, plan=pl, seed=3)
        # knob override on a pinned plan without the flag
        pl2 = linalg.plan(jnp.asarray(A), 8)
        with pytest.raises(ValueError, match="validate"):
            linalg.decompose(jnp.asarray(A), 8, plan=pl2, seed=3,
                             validate=True)


# ---------------------------------------------------------------------------
# serve-layer isolation (satellite: one bad leaf must not sink the tree)


class TestLowrankIsolation:
    def test_poisoned_leaf_stays_dense_others_compress(self):
        from repro.serve.lowrank import factorize_params

        good = _gauss(96, 64, seed=2)
        bad = np.array(_gauss(96, 64, seed=3))
        bad[5, 5] = np.nan
        params = {"a": {"w_up": jnp.asarray(good)},
                  "b": {"w_up": jnp.asarray(bad)}}
        out, report = factorize_params(params, rank=8)
        assert set(out["a"]["w_up"]) == {"lr_a", "lr_b"}  # factorized
        assert isinstance(out["b"]["w_up"], jnp.ndarray)  # kept dense
        assert np.isnan(report["b/w_up"])
        assert np.isfinite(report["a/w_up"])
