"""Fused one-pass kernels (power step, sketch+gram, TRSM) vs pure-jnp
oracles, plus the end-to-end fused/backends equivalences on all three
execution scales (dense / blocked / distributed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import qr as qr_mod
from repro.core.sketch import sketch_matrix
from repro.kernels import ops, ref


def _rand(shape, seed, dtype=jnp.float32):
    flat = sketch_matrix(int(np.prod(shape[:-1])), shape[-1], seed)
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# fused two-sided power step: (Y, Z[, G]) = (A X, Aᵀ Y[, Yᵀ Y])
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m,n,s", [(64, 48, 16), (128, 128, 32), (130, 100, 17), (100, 257, 20)]
)
@pytest.mark.parametrize("with_gram", [False, True])
def test_power_step_matches_oracle(m, n, s, with_gram):
    a = _rand((m, n), 0)
    x = _rand((n, s), 1)
    got = ops.power_step(a, x, with_gram=with_gram)
    want = ref.power_step_ref(a, x, with_gram=with_gram)
    for g, w in zip(got, want):
        # fp32 tiled accumulation reorders sums vs the oracle: relative tol
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-4, rtol=2e-3
        )


def test_power_step_bf16_fp32_accum():
    """bf16 inputs accumulate in fp32 in-kernel: the result must track the
    fp32-accumulating oracle to bf16 output resolution."""
    a = _rand((100, 70), 2, jnp.bfloat16)
    x = _rand((70, 12), 3, jnp.bfloat16)
    y, z, g = ops.power_step(a, x, with_gram=True)
    yr, zr, gr = ref.power_step_ref(a, x, with_gram=True)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=2e-1, rtol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(z, np.float32), np.asarray(zr, np.float32), atol=2e0, rtol=2e-2
    )
    # G output is always fp32
    assert g.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=2e0, rtol=2e-2)


@settings(deadline=None, max_examples=8)
@given(m=st.integers(2, 150), n=st.integers(2, 120), s=st.integers(1, 32),
       seed=st.integers(0, 1000))
def test_power_step_property(m, n, s, seed):
    a = _rand((m, n), seed)
    x = _rand((n, s), seed + 1)
    y, z = ops.power_step(a, x)
    yr, zr = ref.power_step_ref(a, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# sketch + gram epilogue: (Y, G) in one pass over A
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,s", [(64, 64, 16), (100, 90, 17), (128, 256, 32)])
@pytest.mark.parametrize("kind", ["gaussian", "rademacher"])
def test_sketch_gram_matches_oracle(m, n, s, kind):
    a = _rand((m, n), 4)
    y, g = ops.sketch_gram(a, s, seed=7, kind=kind)
    yr, gr = ref.sketch_gram_ref(a, s, seed=7, kind=kind)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-2, rtol=1e-4)
    # G is exactly symmetric (single accumulator, no reconstruction)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g).T)


def test_sketch_gram_bf16_fp32_accum():
    a = _rand((96, 80), 5, jnp.bfloat16)
    y, g = ops.sketch_gram(a, 10, seed=3)
    yr, gr = ref.sketch_gram_ref(a, 10, seed=3)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=5e-1, rtol=2e-2
    )
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=5e0, rtol=3e-2)


@settings(deadline=None, max_examples=8)
@given(m=st.integers(2, 150), n=st.integers(2, 120), s=st.integers(1, 32),
       seed=st.integers(0, 1000))
def test_sketch_gram_property(m, n, s, seed):
    a = _rand((m, n), seed)
    y, g = ops.sketch_gram(a, s, seed=seed)
    yr, gr = ref.sketch_gram_ref(a, s, seed=seed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-2, rtol=1e-3)


# ---------------------------------------------------------------------------
# sketch_power: (Y, W, G) = (A Ω, Aᵀ Y, Yᵀ Y) in one pass, Ω in VMEM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,s", [(64, 48, 16), (130, 100, 17), (128, 256, 32)])
@pytest.mark.parametrize("kind", ["gaussian", "rademacher"])
def test_sketch_power_matches_oracle(m, n, s, kind):
    a = _rand((m, n), 30)
    got = ops.sketch_power(a, s, seed=5, kind=kind)
    want = ref.sketch_power_ref(a, s, seed=5, kind=kind)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-3, rtol=2e-3)


@settings(deadline=None, max_examples=8)
@given(m=st.integers(2, 150), n=st.integers(2, 120), s=st.integers(1, 32),
       seed=st.integers(0, 1000))
def test_sketch_power_property(m, n, s, seed):
    a = _rand((m, n), seed)
    got = ops.sketch_power(a, s, seed=seed)
    want = ref.sketch_power_ref(a, s, seed=seed)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-3, rtol=2e-3)


def test_fused_power_vmem_guard_falls_back():
    """Shapes whose strip working set exceeds the VMEM budget must route to
    the unfused body (the kernel would not compile on real hardware)."""
    from repro.core import RSVDConfig
    from repro.core.rsvd import _use_fused_power
    from repro.kernels.power_step import fused_power_vmem_bytes
    from repro.roofline import hw

    cfg = RSVDConfig.fast()
    small = jnp.zeros((512, 256), jnp.float32)
    assert _use_fused_power(small, cfg, s=34)
    big = jax.ShapeDtypeStruct((16384, 16384), jnp.float32)
    assert fused_power_vmem_bytes(16384, 384) > hw.VMEM_BYTES
    assert not _use_fused_power(big, cfg, s=266)


# ---------------------------------------------------------------------------
# TRSM kernel: Q = Y R⁻¹
# ---------------------------------------------------------------------------

def _spd_r(s, seed, dtype=jnp.float32):
    y = _rand((4 * s, s), seed)
    g = np.asarray(ref.gram_ref(y, jnp.float32)) + s * np.eye(s, dtype=np.float32)
    return jnp.asarray(np.linalg.cholesky(g).T).astype(dtype)


@pytest.mark.parametrize("m,s", [(64, 16), (130, 17), (256, 40), (100, 130)])
def test_trsm_matches_oracle(m, s):
    y = _rand((m, s), 6)
    r = _spd_r(s, 7)
    got = ops.tri_solve_right(y, r)
    want = ref.tri_solve_right_ref(y, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-3)


@settings(deadline=None, max_examples=8)
@given(m=st.integers(2, 150), s=st.integers(1, 48), seed=st.integers(0, 1000))
def test_trsm_property(m, s, seed):
    y = _rand((m, s), seed)
    r = _spd_r(s, seed + 1)
    np.testing.assert_allclose(
        np.asarray(ops.tri_solve_right(y, r)),
        np.asarray(ref.tri_solve_right_ref(y, r)),
        atol=2e-4, rtol=1e-3,
    )


# ---------------------------------------------------------------------------
# traced sketch seed: one compiled program across seeds / offsets / vmap
# ---------------------------------------------------------------------------

def test_sketch_seed_is_traced_no_recompile():
    a = _rand((64, 64), 8)
    before_any = ops.sketch_matmul(a, 9, seed=1)
    size0 = ops.sketch_matmul._cache_size()
    for seed in (2, 3, 4):
        got = ops.sketch_matmul(a, 9, seed=seed)
        want = ref.sketch_matmul_ref(a, 9, seed=seed)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    # seed sweeps reuse the compiled program (seed is an SMEM operand)
    assert ops.sketch_matmul._cache_size() == size0
    np.testing.assert_allclose(
        np.asarray(before_any), np.asarray(ref.sketch_matmul_ref(a, 9, seed=1)),
        atol=1e-4, rtol=1e-4,
    )


def test_sketch_vmap_over_seeds():
    """The batched path's contract: vmapping the fused sketch over per-slice
    seeds equals a per-slice loop of materialized sketches."""
    a = _rand((3, 48, 64), 9)
    seeds = jnp.asarray([5, 6, 7], jnp.uint32)
    got = jax.vmap(lambda x, sd: ops.sketch_matmul(x, 11, sd))(a, seeds)
    for i in range(3):
        want = ref.sketch_matmul_ref(a[i], 11, seed=5 + i)
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want), atol=1e-4, rtol=1e-4
        )


# ---------------------------------------------------------------------------
# end-to-end: fused one-pass range finder == unfused (dense path)
# ---------------------------------------------------------------------------

def _cfgs(**kw):
    from repro.core import RSVDConfig

    return RSVDConfig(power_scheme="stabilized", qr_method="cqr2",
                      small_svd="lapack", **kw)


def test_fused_power_matches_unfused_dense():
    # "fast" has distinct singular values, so A_k (hence the reconstruction)
    # is unique and comparable; "sharp" cuts inside a degenerate cluster
    # where any rotated basis is an equally valid answer.
    from repro import linalg
    from repro.core.spectra import make_test_matrix

    A, _ = make_test_matrix(300, 200, "fast", seed=10)
    k = 16
    U0, S0, Vt0 = linalg.svd(A, k, overrides=_cfgs())
    U1, S1, Vt1 = linalg.svd(
        A, k,
        overrides=_cfgs(fused_sketch=True, fused_power=True, kernel_backend="pallas"),
    )
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=2e-4)
    r0 = np.asarray((U0 * S0[None, :]) @ Vt0)
    r1 = np.asarray((U1 * S1[None, :]) @ Vt1)
    assert np.linalg.norm(r1 - r0) / np.linalg.norm(np.asarray(A)) < 1e-4
    np.testing.assert_allclose(np.asarray(U1.T @ U1), np.eye(k), atol=5e-5)


def test_fused_power_plain_scheme_matches_unfused():
    """The ablation path: the plain GEMM chain through the fused kernel."""
    from repro import linalg
    from repro.core import RSVDConfig
    from repro.core.spectra import make_test_matrix

    A, _ = make_test_matrix(200, 128, "sharp", seed=11)
    k = 10
    base = RSVDConfig(power_scheme="plain", power_iters=1, qr_method="cqr2",
                      small_svd="lapack")
    U0, S0, Vt0 = linalg.svd(A, k, overrides=base)
    U1, S1, Vt1 = linalg.svd(
        A, k,
        overrides=RSVDConfig(power_scheme="plain", power_iters=1, qr_method="cqr2",
                             small_svd="lapack", fused_power=True),
    )
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=2e-4)


def test_fused_power_zero_iters():
    """power_iters=0 must still work through the fused body (no W)."""
    from repro import linalg
    from repro.core import low_rank_error
    from repro.core.spectra import make_test_matrix

    A, _ = make_test_matrix(128, 96, "fast", seed=12)
    cfg = _cfgs(power_iters=0, fused_sketch=True, fused_power=True,
                kernel_backend="pallas")
    U, S, Vt = linalg.svd(A, 8, overrides=cfg)
    assert float(low_rank_error(A, U, S, Vt)) < 0.5
    np.testing.assert_allclose(np.asarray(U.T @ U), np.eye(8), atol=5e-5)


def test_fused_f64_falls_back_to_unfused():
    """float64 (the faithful setting) must silently bypass the fp32 kernels."""
    from repro import linalg
    from repro.compat import enable_x64
    from repro.core.spectra import make_test_matrix

    with enable_x64():
        A, _ = make_test_matrix(128, 96, "sharp", seed=13, dtype=jnp.float64)
        k = 8
        U0, S0, _ = linalg.svd(A, k, overrides=_cfgs())
        U1, S1, _ = linalg.svd(
            A, k,
            overrides=_cfgs(fused_sketch=True, fused_power=True, kernel_backend="pallas"),
        )
        assert S1.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=1e-12)


# ---------------------------------------------------------------------------
# kernel backend parity on all three execution scales
# ---------------------------------------------------------------------------

def test_backend_pallas_dense_matches_jnp():
    from repro import linalg
    from repro.core.spectra import make_test_matrix

    A, _ = make_test_matrix(256, 96, "fast", seed=14)
    k = 10
    U0, S0, Vt0 = linalg.svd(A, k, overrides=_cfgs(kernel_backend="jnp"))
    U1, S1, Vt1 = linalg.svd(A, k, overrides=_cfgs(kernel_backend="pallas"))
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=2e-5)
    r0 = np.asarray((U0 * S0[None, :]) @ Vt0)
    r1 = np.asarray((U1 * S1[None, :]) @ Vt1)
    assert np.linalg.norm(r1 - r0) / np.linalg.norm(np.asarray(A)) < 1e-4


def test_backend_pallas_blocked_matches_jnp():
    from repro import linalg
    from repro.core import RSVDConfig
    from repro.core.spectra import make_test_matrix

    A, _ = make_test_matrix(384, 96, "sharp", seed=15)
    k = 10
    cfg0 = RSVDConfig.streaming(block_rows=100)
    # pallas backend + fused whole-panel sketch: the sketch_gram epilogue
    # feeds the first blocked-CQR2 Gram (no re-read of the Y panels)
    cfg1 = RSVDConfig(power_scheme="stabilized", qr_method="cqr2",
                      small_svd="lapack", block_rows=100,
                      kernel_backend="pallas", fused_sketch=True)
    U0, S0, Vt0 = linalg.svd(A, k, overrides=cfg0, seed=0)
    U1, S1, Vt1 = linalg.svd(A, k, overrides=cfg1, seed=0)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(U1.T @ U1), np.eye(k), atol=5e-5)


def test_backend_pallas_distributed_matches_jnp():
    """shard_map CQR through the Pallas kernels == plain (multi-device CI)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (CI sets xla_force_host_platform_device_count)")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import linalg
    from repro.core import RSVDConfig
    from repro.core.spectra import make_test_matrix

    n_dev = len(jax.devices())
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    A, _ = make_test_matrix(32 * n_dev, 64, "sharp", seed=16)
    A_sharded = jax.device_put(A, NamedSharding(mesh, P("data", None)))
    k = 8
    op = linalg.ShardedOp(A_sharded, mesh, "data")
    cfg0 = RSVDConfig(power_iters=1, kernel_backend="jnp")
    cfg1 = RSVDConfig(power_iters=1, kernel_backend="pallas")
    _, S0, _ = linalg.svd(op, k, overrides=cfg0)
    U1, S1, Vt1 = linalg.svd(op, k, overrides=cfg1)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.asarray(U1).T @ jnp.asarray(U1)), np.eye(k), atol=5e-5
    )


def test_qr_gram_trsm_backend_parity():
    """The backend seam itself: qr.gram / qr.tri_solve_right under the
    pallas context == the jnp defaults."""
    y = _rand((200, 24), 17)
    g0 = qr_mod.gram(y)
    with qr_mod.kernel_backend("pallas"):
        g1 = qr_mod.gram(y)
        r = qr_mod.cholesky_r_from_gram(g1)
        q1 = qr_mod.tri_solve_right(y, r)
    q0 = ref.tri_solve_right_ref(y, r)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q0), atol=2e-4, rtol=1e-3)
    # context restored
    assert qr_mod.active_kernel_backend() == "jnp"


def test_blocked_fused_sketch_f64_falls_back():
    """Blocked streaming with fused_sketch on f64 input must stay on the jnp
    sketch (and in f64), like the dense path's guard."""
    from repro import linalg
    from repro.compat import enable_x64
    from repro.core import RSVDConfig
    from repro.core.spectra import make_test_matrix

    with enable_x64():
        A, _ = make_test_matrix(256, 64, "fast", seed=18, dtype=jnp.float64)
        cfg0 = RSVDConfig.streaming(block_rows=100)
        cfg1 = RSVDConfig(power_scheme="stabilized", qr_method="cqr2",
                          small_svd="lapack", block_rows=100, fused_sketch=True)
        U0, S0, _ = linalg.svd(A, 8, overrides=cfg0, seed=0)
        U1, S1, _ = linalg.svd(A, 8, overrides=cfg1, seed=0)
        assert S1.dtype == jnp.float64 and U1.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(S1), np.asarray(S0), rtol=1e-12)


def test_blocked_cholesky_qr_bf16_panels_keep_dtype():
    """The blocked CQR pass factors/solves at fp32 (LAPACK has no bf16
    Cholesky/TRSM) but must hand back Q panels in the panel dtype, whether
    the Gram came from the panels or from the fp32 sketch_gram epilogue."""
    from repro.core.blocked import _blocked_cholesky_qr

    panels = [_rand((64, 12), 40 + i, jnp.bfloat16) for i in range(3)]
    Q, R = _blocked_cholesky_qr(panels)
    assert all(q.dtype == jnp.bfloat16 for q in Q)
    g = sum(np.asarray(p, np.float32).T @ np.asarray(p, np.float32) for p in panels)
    Q2, _ = _blocked_cholesky_qr(panels, jnp.asarray(g))  # epilogue-style fp32 G
    assert all(q.dtype == jnp.bfloat16 for q in Q2)
    stacked = np.concatenate([np.asarray(q, np.float32) for q in Q2])
    np.testing.assert_allclose(stacked.T @ stacked, np.eye(12), atol=0.1)


# ---------------------------------------------------------------------------
# batched path with the fused sketch
# ---------------------------------------------------------------------------

def test_batched_fused_sketch_matches_loop():
    from repro import linalg
    from repro.core import RSVDConfig
    from repro.core.spectra import make_test_matrix

    A = jnp.stack([make_test_matrix(96, 48, "fast", seed=20 + i)[0] for i in range(3)])
    k, seed = 6, 11
    cfg = RSVDConfig(power_scheme="stabilized", qr_method="cqr2",
                     small_svd="lapack", fused_sketch=True)
    Ub, Sb, Vtb = linalg.svd(linalg.StackedOp(A), k, overrides=cfg, seed=seed)
    for i in range(3):
        Ui, Si, Vti = linalg.svd(A[i], k, overrides=cfg, seed=seed + i)
        np.testing.assert_allclose(np.asarray(Sb[i]), np.asarray(Si), rtol=2e-5)
        ri = np.asarray((Ui * Si[None, :]) @ Vti)
        rb = np.asarray((Ub[i] * Sb[i][None, :]) @ Vtb[i])
        np.testing.assert_allclose(rb, ri, atol=2e-4)
