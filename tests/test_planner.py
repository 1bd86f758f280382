"""Execution-planner golden tests + plan/roofline/facade properties.

Golden table: (source, dtype, overrides) -> expected ExecutionPlan fields,
including the paper's benchmark shapes, where `plan()` must reproduce the
historical `fast()` / `streaming()` dispatch decisions (the VMEM gate that
un-fuses the power step at 16384x16384 included).  Plans are shape-only, so
the big shapes use jax.ShapeDtypeStruct — nothing is allocated.

Properties:
  * every plan's predicted HBM bytes equals the roofline model
    (repro/roofline/rsvd_model.py) evaluated at the plan's own fields;
  * `linalg.svd` on DenseOp / HostOp / StackedOp / ShardedOp returns
    BIT-identical factors to the pre-facade dense / blocked / batched /
    distributed implementations at fixed seed;
  * CenteredOp-based PCA equals `pca_exact` on small inputs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import linalg
from repro.core.rsvd import RSVDConfig
from repro.core.spectra import make_test_matrix
from repro.roofline import rsvd_model


def _sds(m, n, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((m, n), dtype)


# ---------------------------------------------------------------------------
# Golden dispatch table
# ---------------------------------------------------------------------------

# (label, op-builder, overrides, expected plan fields)
GOLDEN = [
    # The paper's benchmark shapes under the fast() preset: fused everywhere
    # the VMEM budget allows, unfused where the n x s accumulators blow it.
    ("fast_2000", lambda: linalg.DenseOp(_sds(2000, 2000)), RSVDConfig.fast(), 90,
     dict(path="dense", fused_power=True, fused_sketch=True,
          kernel_backend="pallas", qr_method="cqr2", s=100, pipeline_depth=1)),
    # 8192², s=256 needs ~74 MiB of the 128 MiB the kernels compile with
    ("fast_8192_vmem_gate", lambda: linalg.DenseOp(_sds(8192, 8192)), RSVDConfig.fast(), 246,
     dict(path="dense", fused_power=True, fused_sketch=True,
          kernel_backend="pallas", s=256)),
    # 16384², s=256 needs ~146 MiB: past the limit, the power step un-fuses
    ("fast_16384_vmem_gate", lambda: linalg.DenseOp(_sds(16384, 16384)), RSVDConfig.fast(), 246,
     dict(path="dense", fused_power=False, fused_sketch=True,
          kernel_backend="pallas", s=256)),
    ("fast_65536x4096", lambda: linalg.DenseOp(_sds(65536, 4096)), RSVDConfig.fast(), 118,
     dict(path="dense", fused_power=True, m=65536, n=4096, s=128)),
    # streaming() preset: panel-streamed, CQR2, no fusion of the power step
    # streamed plans double-buffer the panel prefetch by default (the
    # quarter-HBM budget fits 2 staging panels comfortably at this shape)
    ("streaming_65536x4096", lambda: linalg.DenseOp(_sds(65536, 4096)),
     RSVDConfig.streaming(), 118,
     dict(path="streamed", block_rows=4096, qr_method="cqr2",
          small_svd="lapack", fused_power=False, pipeline_depth=2)),
    # an explicit depth override is the starting point (still clamped by the
    # panel count AND the quarter-HBM budget rule; 3 x 64MB panels fit here)
    ("streaming_depth_override", lambda: linalg.DenseOp(_sds(65536, 4096)),
     dataclasses.replace(RSVDConfig.streaming(), pipeline_depth=3), 118,
     dict(path="streamed", pipeline_depth=3)),
    # f64 faithful: everything un-fused, jnp backend (paper's dgesvd setting)
    ("faithful_f64", lambda: linalg.DenseOp(_sds(300, 200, jnp.float64)),
     RSVDConfig.faithful(), 20,
     dict(path="dense", fused_power=False, fused_sketch=False,
          kernel_backend="jnp", qr_method="householder", dtype="float64")),
    # wide input: the plan records the post-orientation (tall) dims
    ("wide_orientation", lambda: linalg.DenseOp(_sds(128, 4096)), RSVDConfig(), 16,
     dict(path="dense", m=4096, n=128, s=26)),
    # 3-D stack -> batched, power fusion never applies under vmap
    ("stacked", lambda: linalg.StackedOp(jnp.zeros((4, 128, 64))), RSVDConfig.fast(), 8,
     dict(path="batched", batch=4, fused_power=False)),
    # explicit batched override on 2-D input still PLANS batched (execution
    # raises, matching the historical loud failure)
    ("batched_flag", lambda: linalg.DenseOp(_sds(128, 64)),
     RSVDConfig(batched=True), 8, dict(path="batched")),
]


@pytest.mark.parametrize("label,mk_op,overrides,k,expect",
                         GOLDEN, ids=[g[0] for g in GOLDEN])
def test_plan_golden(label, mk_op, overrides, k, expect):
    pl = linalg.plan(mk_op(), k, overrides=overrides)
    for field, want in expect.items():
        assert getattr(pl, field) == want, (label, field, getattr(pl, field), want)


def test_plan_streamed_default_panel_sized_on_oriented_rows():
    """Wide host sources stream A.T, so the default panel-shrink must size
    panels by the SHORT dim — a (1024 x 1e6) host array keeps the 4096
    default instead of over-shrinking to the 256 floor."""
    wide = linalg.DenseOp(_sds(1024, 1_000_000),
                          block_rows=linalg.HostOp.DEFAULT_BLOCK_ROWS)
    pl = linalg.plan(wide, 16)
    assert pl.path == "streamed" and pl.block_rows == linalg.HostOp.DEFAULT_BLOCK_ROWS


def test_plan_defaults_host_source_streams():
    A_host = np.zeros((512, 96), np.float32)
    pl = linalg.plan(linalg.HostOp(A_host, block_rows=128), 8)
    assert pl.path == "streamed" and pl.block_rows == 128
    # and without an explicit panel height the streaming default applies
    pl2 = linalg.plan(linalg.HostOp(A_host), 8)
    assert pl2.path == "streamed" and pl2.block_rows == linalg.HostOp.DEFAULT_BLOCK_ROWS


def test_plan_defaults_composed_source_is_matfree():
    op = linalg.CenteredOp(linalg.DenseOp(jnp.zeros((64, 16))))
    pl = linalg.plan(op, 4)
    assert pl.path == "matfree" and not pl.fused_power and not pl.fused_sketch


def test_plan_sharded_source():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    pl = linalg.plan(linalg.ShardedOp(_sds(256, 64), mesh, "data"), 8)
    assert pl.path == "sharded"


def test_plan_sharded_records_what_the_shard_body_executes():
    """The shard_map body hardcodes CQR2 + LAPACK small SVD + materialized
    per-shard Omega; a fast() override must not make the plan claim
    gram_jacobi or a fused sketch that never runs."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    op = linalg.ShardedOp(_sds(256, 64), mesh, "data")
    pl = linalg.plan(op, 8, overrides=RSVDConfig.fast())
    assert pl.small_svd == "lapack" and pl.qr_method == "cqr2"
    assert not pl.fused_sketch and not pl.fused_power


def test_plan_f64_records_jnp_backend():
    """qr.py vetoes the fp32-accumulating Pallas primitives for float64, so
    an f64 plan must record kernel_backend='jnp' even under fast()."""
    pl = linalg.plan(linalg.DenseOp(_sds(300, 200, jnp.float64)), 20,
                     overrides=RSVDConfig.fast())
    assert pl.kernel_backend == "jnp" and not pl.fused_sketch


def test_plan_vmem_budget_is_honored():
    """Shrinking the budget must flip the 2000x2000 fast() plan to unfused —
    the same gate the dense body applies, parameterized by Budget."""
    op = linalg.DenseOp(_sds(2000, 2000))
    tight = linalg.Budget(vmem_bytes=1 << 20)
    assert linalg.plan(op, 90, overrides=RSVDConfig.fast()).fused_power
    assert not linalg.plan(op, 90, budget=tight, overrides=RSVDConfig.fast()).fused_power


def test_plan_vmem_budget_cannot_loosen_past_kernel_limit():
    """A LOOSER budget must not make the plan claim a fusion the dense
    body's compiled-in VMEM gate would refuse at trace time (the plan is a
    record of what executes, never a wish)."""
    op = linalg.DenseOp(_sds(16384, 16384))
    loose = linalg.Budget(vmem_bytes=1 << 30)
    pl = linalg.plan(op, 246, budget=loose, overrides=RSVDConfig.fast())
    assert not pl.fused_power


def test_protocol_only_source_runs_matfree_even_with_overrides():
    """A user-defined LinOp (no .array) must plan matfree whether or not
    overrides pin the numerical variant."""

    class GramOp(linalg.LinOp):
        def __init__(self, A):
            self._A = A

        @property
        def shape(self):
            return tuple(self._A.shape)

        @property
        def dtype(self):
            return self._A.dtype

        def matmat(self, X):
            return self._A @ X

        def rmatmat(self, Y):
            return self._A.T @ Y

    A, sig = make_test_matrix(200, 64, "fast", seed=9)
    op = GramOp(A)
    cfg = RSVDConfig(power_iters=1, qr_method="cqr2")
    assert linalg.plan(op, 8).path == "matfree"
    assert linalg.plan(op, 8, overrides=cfg).path == "matfree"
    U, S, Vt = linalg.svd(op, 8, overrides=cfg, seed=1)
    err = float(linalg.residual(A, (U, S, Vt)))
    from repro.core import truncation_error

    assert err <= 1.10 * float(truncation_error(sig, 8)) + 1e-6


def test_eigvals_matfree_sigma_only_matches_svd():
    op = linalg.CenteredOp(linalg.DenseOp(make_test_matrix(96, 32, "fast", seed=10)[0]))
    S_full = linalg.svd(op, 6, seed=2)[1]
    S_only = linalg.eigvals(op, 6, seed=2)
    np.testing.assert_array_equal(np.asarray(S_only), np.asarray(S_full))


def test_hostop_keeps_streaming_under_numerical_overrides():
    """Overrides that pin only the numerical variant (no block_rows) must
    not collapse an explicit HostOp onto the wholesale-dense path."""
    A_host = np.asarray(make_test_matrix(256, 48, "fast", seed=11)[0])
    op = linalg.HostOp(A_host, block_rows=64)
    cfg = RSVDConfig(power_iters=1, qr_method="cqr2")  # no execution switches
    pl = linalg.plan(op, 8, overrides=cfg)
    assert pl.path == "streamed" and pl.block_rows == 64
    U, S, Vt = linalg.svd(op, 8, overrides=cfg, seed=0)
    assert float(linalg.residual(op, (U, S, Vt))) < 0.2


def test_pca_dense_path_is_jitted_and_matches_eager():
    """Device-array PCA runs one compiled program (seed traced — sweeps
    don't recompile) and equals the eager CenteredOp pipeline."""
    from repro.linalg.api import _pca_centered_dense

    X = make_test_matrix(96, 32, "fast", seed=12)[0] + 0.25
    r0 = linalg.pca(X, 4, seed=0)
    size0 = _pca_centered_dense._cache_size()
    r1 = linalg.pca(X, 4, seed=1)
    assert _pca_centered_dense._cache_size() == size0  # traced seed, no recompile
    eager = linalg.svd(linalg.CenteredOp(linalg.DenseOp(X)), 4, seed=1)
    np.testing.assert_allclose(np.asarray(r1.singular_values), np.asarray(eager[1]),
                               rtol=1e-5)
    assert r0.components.shape == (4, 32)


def test_plan_matches_dense_body_gate():
    """plan().fused_power must agree with core.rsvd._use_fused_power (the
    dense body's trace-time gate) on a sweep of shapes."""
    from repro.core.rsvd import _use_fused_power

    cfg = RSVDConfig.fast()
    for m, n in [(256, 128), (2000, 2000), (8192, 8192), (4096, 512), (512, 4096)]:
        k = 16
        mt, nt = max(m, n), min(m, n)
        s = min(k + cfg.oversample, nt)
        pl = linalg.plan(linalg.DenseOp(_sds(m, n)), k, overrides=cfg)
        want = _use_fused_power(_sds(mt, nt), cfg, s)
        assert pl.fused_power == want, (m, n, pl.fused_power, want)


# ---------------------------------------------------------------------------
# Property: predicted HBM bytes == the roofline model at the plan's fields
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mk_op,overrides,k", [
    (lambda: linalg.DenseOp(_sds(2000, 2000)), RSVDConfig.fast(), 90),
    (lambda: linalg.DenseOp(_sds(8192, 8192)), RSVDConfig.fast(), 246),
    (lambda: linalg.DenseOp(_sds(128, 4096)), RSVDConfig(), 16),
    (lambda: linalg.DenseOp(_sds(300, 200, jnp.float64)), RSVDConfig.faithful(), 20),
    (lambda: linalg.StackedOp(jnp.zeros((4, 128, 64))), RSVDConfig(), 8),
    (lambda: linalg.DenseOp(_sds(65536, 4096)), RSVDConfig.streaming(), 118),
])
def test_predicted_bytes_match_roofline_model(mk_op, overrides, k):
    pl = linalg.plan(mk_op(), k, overrides=overrides)
    want = rsvd_model.predicted_hbm_bytes(
        pl.m, pl.n, pl.s, pl.power_iters, pl.fused_power, pl.fused_sketch,
        dtype_bytes=jnp.dtype(pl.dtype).itemsize, batch=pl.batch,
    )
    assert pl.predicted_hbm_bytes == want
    # the walltime prediction comes from the SAME model, at the plan's own
    # fields: the overlap model for streamed plans, HBM bandwidth elsewhere
    if pl.path == "streamed":
        want_t = rsvd_model.streamed_walltime_s(
            pl.m, pl.n, pl.s, pl.block_rows, pl.power_iters, pl.pipeline_depth,
            dtype_bytes=jnp.dtype(pl.dtype).itemsize, fused_sketch=pl.fused_sketch,
        )
    else:
        want_t = rsvd_model.hbm_walltime_s(pl.predicted_hbm_bytes)
    assert pl.predicted_walltime_s == want_t
    # and the fused plan must predict strictly less traffic than unfused
    if pl.fused_power:
        unfused = rsvd_model.predicted_hbm_bytes(
            pl.m, pl.n, pl.s, pl.power_iters, False, False,
            dtype_bytes=jnp.dtype(pl.dtype).itemsize, batch=pl.batch)
        assert pl.predicted_hbm_bytes < unfused


# ---------------------------------------------------------------------------
# Acceptance: facade factors are BIT-identical to the pre-facade paths
# ---------------------------------------------------------------------------

def _assert_same(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_denseop_bit_identical_to_dense_path():
    from repro.core import rsvd as rsvd_mod

    A, _ = make_test_matrix(256, 96, "fast", seed=1)
    cfg = RSVDConfig(power_scheme="stabilized", qr_method="cqr2")
    got = linalg.svd(linalg.DenseOp(A), 10, overrides=cfg, seed=3)
    want = rsvd_mod._randomized_svd_dense(A, jnp.uint32(3), 10, cfg)
    _assert_same(got, want)


def test_hostop_bit_identical_to_blocked_path():
    from repro.core.blocked import svd_streamed

    A_host = np.asarray(make_test_matrix(300, 64, "fast", seed=2)[0])
    cfg = RSVDConfig.streaming(block_rows=100)
    got = linalg.svd(linalg.HostOp(A_host, block_rows=100), 8, overrides=cfg, seed=1)
    want = svd_streamed(A_host, 8, cfg, seed=1)
    _assert_same(got, want)


def test_stackedop_bit_identical_to_batched_path():
    from repro.core.blocked import svd_batched

    A = jnp.stack([make_test_matrix(96, 48, "fast", seed=3 + i)[0] for i in range(3)])
    cfg = RSVDConfig()
    got = linalg.svd(linalg.StackedOp(A), 6, overrides=cfg, seed=4)
    want = svd_batched(A, 6, cfg, seed=4)
    _assert_same(got, want)


def test_shardedop_bit_identical_to_distributed_path():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import svd_sharded

    n_dev = len(jax.devices())
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    A, _ = make_test_matrix(32 * n_dev, 64, "fast", seed=5)
    A_sharded = jax.device_put(A, NamedSharding(mesh, P("data", None)))
    cfg = RSVDConfig(power_iters=1)
    got = linalg.svd(linalg.ShardedOp(A_sharded, mesh, "data"), 8, overrides=cfg, seed=0)
    want = svd_sharded(A_sharded, 8, mesh, "data", cfg, seed=0)
    _assert_same(got, want)


# ---------------------------------------------------------------------------
# Property: CenteredOp-based PCA == pca_exact on small inputs
# ---------------------------------------------------------------------------

def test_centered_pca_matches_exact():
    from repro.core.pca import pca_exact

    X, _ = make_test_matrix(160, 40, "fast", seed=7)
    X = X + 0.5  # a nonzero mean so the centering actually matters
    k = 5
    res = linalg.pca(X, k)
    exact = pca_exact(X, k)
    np.testing.assert_allclose(np.asarray(res.mean), np.asarray(exact.mean), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(res.explained_variance), np.asarray(exact.explained_variance),
        rtol=2e-3,
    )
    # the spanned subspace agrees: compare the basis-invariant projectors
    P_got = np.asarray(res.components).T @ np.asarray(res.components)
    P_want = np.asarray(exact.components).T @ np.asarray(exact.components)
    np.testing.assert_allclose(P_got, P_want, atol=2e-3)


def test_centered_pca_streams_host_input():
    """The centered HOST source: mu and the factors come out right without
    the centered matrix (or X itself) ever being device-resident whole."""
    from repro.core.pca import pca_exact

    X = np.asarray(make_test_matrix(256, 32, "fast", seed=8)[0]) + 1.0
    res = linalg.pca(linalg.HostOp(X, block_rows=64), 4)
    exact = pca_exact(jnp.asarray(X), 4)
    np.testing.assert_allclose(np.asarray(res.mean), np.asarray(exact.mean), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(res.singular_values), np.asarray(exact.singular_values), rtol=5e-3
    )
