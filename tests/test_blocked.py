"""Blocked/streaming + batched randomized SVD vs. the dense in-memory path,
all through the `repro.linalg` facade (HostOp / StackedOp / overrides).

Covers the DESIGN.md §"Blocked & batched execution" contracts:
  * panel streaming reproduces the dense result for dividing AND non-dividing
    block_rows (the acceptance case: 4096x512 at block_rows=256, <=1e-4);
  * the (1+eps) near-optimality guarantee survives blocking;
  * the batched vmap path equals a per-slice Python loop, in both the tall
    and the wide (orientation-swap) layouts;
  * the streamed sketch accumulation (panel-offset counter RNG) equals the
    monolithic sketch;
  * each orthonormalization step between two passes is one compiled
    program that matches its eager composition, traced once per shape and
    called 2q + 1 times a solve.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import linalg
from repro.core import RSVDConfig, low_rank_error, streamed_sketch, truncation_error
from repro.core import blocked
from repro.core import qr as qr_mod
from repro.core.spectra import make_test_matrix
from repro.kernels import ref

BASE = RSVDConfig()  # the historical default variant, pinned on both paths


def _recon(U, S, Vt):
    return np.asarray((U * S[None, :]) @ Vt)


def _rel_fro(X, Y, A):
    return float(np.linalg.norm(X - Y) / np.linalg.norm(np.asarray(A)))


# ---------------------------------------------------------------------------
# (a) blocked == unblocked across block sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_rows", [100, 128, 512])  # 100 non-dividing
def test_blocked_matches_dense(block_rows):
    A, _ = make_test_matrix(512, 96, "fast", seed=1)
    k = 12
    U0, S0, Vt0 = linalg.svd(A, k, overrides=BASE)
    U1, S1, Vt1 = linalg.svd(A, k, overrides=RSVDConfig(block_rows=block_rows))
    assert U1.shape == (512, k) and S1.shape == (k,) and Vt1.shape == (k, 96)
    assert _rel_fro(_recon(U0, S0, Vt0), _recon(U1, S1, Vt1), A) <= 1e-4
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=1e-4)


def test_blocked_acceptance_4096x512():
    """The PR acceptance case: block_rows=256 on 4096x512, <=1e-4 rel Fro."""
    A, _ = make_test_matrix(4096, 512, "fast", seed=2)
    k = 16
    cfg = RSVDConfig(power_iters=1, qr_method="cqr2")  # same cfg on both paths
    U0, S0, Vt0 = linalg.svd(A, k, overrides=cfg)
    U1, S1, Vt1 = linalg.svd(
        A, k, overrides=RSVDConfig(power_iters=1, qr_method="cqr2", block_rows=256)
    )
    assert _rel_fro(_recon(U0, S0, Vt0), _recon(U1, S1, Vt1), A) <= 1e-4


def test_host_numpy_plans_streamed_execution():
    """Out-of-core shape: a host numpy array wrapped in HostOp plans the
    streamed path by default, and matches the pinned streaming preset."""
    A_host = np.asarray(make_test_matrix(256, 64, "fast", seed=3)[0])
    op = linalg.HostOp(A_host, block_rows=128)
    assert linalg.plan(op, 8).path == "streamed"
    U, S, Vt = linalg.svd(op, 8)
    U2, S2, Vt2 = linalg.svd(A_host, 8, overrides=RSVDConfig.streaming(block_rows=128))
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S2))
    err = float(linalg.residual(op, (U, S, Vt)))
    assert err < 0.2


# ---------------------------------------------------------------------------
# (b) near-optimality on decaying spectra
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["fast", "sharp"])
def test_blocked_near_optimal_error(kind):
    A, sig = make_test_matrix(384, 96, kind, seed=4)
    k = 16
    cfg = RSVDConfig.streaming(block_rows=100)  # non-dividing on purpose
    U, S, Vt = linalg.svd(A, k, overrides=cfg)
    err = float(low_rank_error(A, U, S, Vt))
    opt = float(truncation_error(sig, k))
    assert err <= 1.10 * opt + 1e-6, (err, opt)


def test_blocked_wide_matrix_orientation_swap():
    """m < n streams the taller side of A^T; factors keep the A orientation."""
    A, _ = make_test_matrix(256, 64, "fast", seed=5)
    At = A.T  # 64 x 256 wide
    U, S, Vt = linalg.svd(At, 10, overrides=RSVDConfig(block_rows=96))
    assert U.shape == (64, 10) and Vt.shape == (10, 256)
    err = float(low_rank_error(At, U, S, Vt))
    S_dense = jnp.linalg.svd(At, compute_uv=False)
    assert err <= 1.10 * float(truncation_error(S_dense, 10)) + 1e-6


# ---------------------------------------------------------------------------
# (c) batched path == Python loop; wide batched
# ---------------------------------------------------------------------------

def _stack(B, m, n, kind="fast"):
    return jnp.stack([make_test_matrix(m, n, kind, seed=10 + i)[0] for i in range(B)])


def test_batched_matches_python_loop():
    A = _stack(4, 96, 48)
    k, seed = 8, 5
    Ub, Sb, Vtb = linalg.svd(A, k, overrides=BASE, seed=seed)
    for i in range(A.shape[0]):
        # slice i sketches with seed + i — the loop equivalent
        Ui, Si, Vti = linalg.svd(A[i], k, overrides=BASE, seed=seed + i)
        np.testing.assert_allclose(np.asarray(Sb[i]), np.asarray(Si), rtol=2e-5)
        np.testing.assert_allclose(
            _recon(Ub[i], Sb[i], Vtb[i]), _recon(Ui, Si, Vti), atol=2e-4
        )


def test_batched_wide_matches_loop():
    A = _stack(3, 40, 120)  # m < n: orientation swap inside the batch
    k = 6
    Ub, Sb, Vtb = linalg.svd(A, k, overrides=BASE, seed=2)
    assert Ub.shape == (3, 40, k) and Vtb.shape == (3, k, 120)
    for i in range(3):
        Ui, Si, Vti = linalg.svd(A[i], k, overrides=BASE, seed=2 + i)
        np.testing.assert_allclose(np.asarray(Sb[i]), np.asarray(Si), rtol=2e-5)
        np.testing.assert_allclose(
            _recon(Ub[i], Sb[i], Vtb[i]), _recon(Ui, Si, Vti), atol=2e-4
        )


def test_three_d_input_plans_batched_path():
    A = _stack(2, 64, 32)
    assert linalg.plan(A, 4, overrides=BASE).path == "batched"
    U3, S3, Vt3 = linalg.svd(A, 4, overrides=BASE, seed=9)       # facade
    from repro.core.blocked import svd_batched

    Ub, Sb, Vtb = svd_batched(A, 4, BASE, seed=9)                # direct
    np.testing.assert_array_equal(np.asarray(S3), np.asarray(Sb))
    np.testing.assert_array_equal(np.asarray(U3), np.asarray(Ub))


def test_batched_override_rejects_2d():
    with pytest.raises(ValueError):
        linalg.svd(jnp.zeros((8, 4)), 2, overrides=RSVDConfig(batched=True))


# ---------------------------------------------------------------------------
# (d) streamed sketch accumulation == monolithic sketch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["gaussian", "rademacher"])
@pytest.mark.parametrize("fused", [False, True])
def test_streamed_sketch_matches_monolithic(kind, fused):
    A, _ = make_test_matrix(64, 96, "fast", seed=6)
    # block_cols=40 leaves a ragged 16-wide last panel on purpose
    got = streamed_sketch(A, 17, seed=3, kind=kind, block_cols=40, fused=fused)
    want = ref.sketch_matmul_ref(A, 17, seed=3, kind=kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# (e) the compiled orthonormalization steps of the streamed engine
# ---------------------------------------------------------------------------

def _check_orth(Q, R, Y, Q_eager):
    """Q orthonormal, QR = Y, and Q within 1e-5 of the eager composition's
    (two programs: no bits pinned between them)."""
    Q, R, Y = (np.asarray(x, np.float64) for x in (Q, R, Y))
    assert np.linalg.norm(Q.T @ Q - np.eye(Q.shape[1])) <= 1e-4
    assert np.linalg.norm(Q @ R - Y) / np.linalg.norm(Y) <= 1e-5
    np.testing.assert_allclose(Q, np.asarray(Q_eager), rtol=0, atol=1e-5)


@pytest.mark.parametrize("cqr3", [False, True], ids=["cqr2", "cqr3"])
@pytest.mark.parametrize("with_g1", [False, True], ids=["no_g1", "g1"])
@pytest.mark.parametrize("rows", [(96,), (40, 40, 17)], ids=["one", "ragged3"])
def test_compiled_panel_orth_matches_eager(rows, with_g1, cqr3):
    rng = np.random.default_rng(sum(rows))
    Y = rng.standard_normal((sum(rows), 12)).astype(np.float32)
    edges = np.cumsum((0,) + rows)
    panels = [jnp.asarray(Y[lo:hi]) for lo, hi in zip(edges[:-1], edges[1:])]
    G1 = jnp.asarray(Y.T @ Y) if with_g1 else None
    Q, R = blocked._orth_panels(panels, G1, cqr3=cqr3, backend="jnp")
    Q_eager, _ = blocked._orth_panels_impl(panels, G1, cqr3, "jnp")
    assert [q.shape for q in Q] == [p.shape for p in panels]
    _check_orth(jnp.concatenate(Q), R, Y, jnp.concatenate(Q_eager))


def test_compiled_panel_orth_falls_back_to_cqr3_on_breakdown():
    """A CQR2 Cholesky that breaks down (here forced once, in a guarded
    trace) yields the shifted CQR3 of the same panels, and the probe still
    reports the breakdown."""
    from repro.linalg import faults, guard

    Y = np.random.default_rng(9).standard_normal((96, 12)).astype(np.float32)
    panels = [jnp.asarray(Y[:50]), jnp.asarray(Y[50:])]
    with guard.collecting(), faults.inject("cholesky_breakdown", times=1):
        (Q, R), probes = blocked._orth_panels_probed(
            panels, None, cqr3=False, backend="jnp",
            fault_key=faults.fingerprint())
    assert bool(probes["breakdown"])
    Q3, _ = blocked._orth_panels_impl(panels, None, True, "jnp")
    _check_orth(jnp.concatenate(Q), R, Y, jnp.concatenate(Q3))


@pytest.mark.parametrize("method", ["cqr2", "cqr3"])
def test_compiled_orthonormalize_matches_eager(method):
    Z = np.random.default_rng(5).standard_normal((80, 12)).astype(np.float32)
    Q = blocked._orthonormalize(jnp.asarray(Z), method=method, backend="jnp")
    Q_eager = qr_mod.orthonormalize(jnp.asarray(Z), method)
    _check_orth(Q, Q.T @ Z, Z, Q_eager)


def test_orth_programs_traced_once_and_called_per_step():
    """Three solves of one HostOp shape under new seeds: each orth program
    is traced exactly once, and `engine.orth.calls` moves by 2q + 1 (the
    panel CQR2 before each power_z pass, orthonormalize(Z) before each
    power_y pass, the final panel CQR2) per solve."""
    from repro.linalg import trace

    A = np.random.default_rng(3).standard_normal((333, 40)).astype(np.float32)
    op = linalg.HostOp(A, block_rows=128)  # a shape no other test streams
    pl = linalg.plan(op, linalg.Rank(6))
    assert pl.power_scheme != "plain"
    before = trace.counters()
    for seed in (11, 12, 13):
        linalg.decompose(op, linalg.Rank(6), seed=seed)
        calls = trace.count("engine.orth.calls") - before.get("engine.orth.calls", 0)
        assert calls == (2 * pl.power_iters + 1) * (seed - 10)
    traced = {name: n for name, n in trace.counters().items()
              if name.startswith("jit.traces.('engine.orth.")
              and n != before.get(name, 0)}
    assert {name.split("'")[1] for name in traced} == {
        "engine.orth.panels", "engine.orth.orthonormalize"}
    assert set(traced.values()) == {1}
