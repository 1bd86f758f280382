"""LinOp operator sources: algebra vs dense references, panel iteration,
composed operators (scaled / centered / low-rank update / deflation), and
the panel-wise residual."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import linalg
from repro.core import low_rank_error
from repro.core.spectra import make_test_matrix


def _rand(m, n, seed):
    from repro.core.sketch import sketch_matrix

    return sketch_matrix(m, n, seed)


# ---------------------------------------------------------------------------
# sources: matmat / rmatmat / row_panels vs the dense array
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wrap", [
    lambda A: linalg.DenseOp(A),
    lambda A: linalg.HostOp(np.asarray(A), block_rows=40),
])
def test_source_products_match_dense(wrap):
    A = _rand(100, 36, 0)
    X = _rand(36, 7, 1)
    Y = _rand(100, 7, 2)
    op = wrap(A)
    assert op.shape == (100, 36) and jnp.dtype(op.dtype) == jnp.float32
    np.testing.assert_allclose(np.asarray(op.matmat(X)), np.asarray(A @ X),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(op.rmatmat(Y)), np.asarray(A.T @ Y),
                               atol=1e-5, rtol=1e-5)
    # panels tile the rows exactly
    stacked = jnp.concatenate(list(op.row_panels()), axis=0)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(A))


def test_transpose_swaps_products():
    A = _rand(64, 24, 3)
    op = linalg.DenseOp(A).T
    assert op.shape == (24, 64)
    X = _rand(64, 5, 4)
    np.testing.assert_allclose(np.asarray(op.matmat(X)), np.asarray(A.T @ X),
                               atol=1e-5, rtol=1e-5)
    assert op.T is not op and op.T.shape == (64, 24)


def test_stacked_op_products():
    A = jnp.stack([_rand(32, 16, i) for i in range(3)])
    op = linalg.StackedOp(A)
    X = _rand(16, 4, 9)
    np.testing.assert_allclose(np.asarray(op.matmat(X)), np.asarray(A @ X),
                               atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError):
        linalg.StackedOp(_rand(8, 4, 0))


def test_as_linop_coercions():
    assert isinstance(linalg.as_linop(jnp.zeros((4, 3))), linalg.DenseOp)
    assert isinstance(linalg.as_linop(np.zeros((4, 3))), linalg.HostOp)
    assert isinstance(linalg.as_linop(jnp.zeros((2, 4, 3))), linalg.StackedOp)
    op = linalg.DenseOp(jnp.zeros((4, 3)))
    assert linalg.as_linop(op) is op
    # clear facade-level errors: bad rank -> ValueError, non-array -> TypeError
    with pytest.raises(ValueError, match="2-D .* or 3-D"):
        linalg.as_linop(jnp.zeros((4,)))
    with pytest.raises(TypeError, match="no .ndim"):
        linalg.as_linop(object())


# ---------------------------------------------------------------------------
# composed operators
# ---------------------------------------------------------------------------

def test_scaled_op():
    A = _rand(48, 20, 5)
    op = linalg.ScaledOp(linalg.DenseOp(A), -2.5)
    X = _rand(20, 3, 6)
    np.testing.assert_allclose(np.asarray(op.matmat(X)), np.asarray(-2.5 * (A @ X)),
                               atol=1e-5, rtol=1e-5)
    stacked = jnp.concatenate(list(op.row_panels(13)), axis=0)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(-2.5 * A),
                               atol=1e-6, rtol=1e-6)


def test_centered_op_equals_materialized_centering():
    A = _rand(80, 24, 7) + 3.0
    op = linalg.CenteredOp(linalg.DenseOp(A))
    Ac = A - jnp.mean(A, axis=0)[None, :]
    np.testing.assert_allclose(np.asarray(op.mu), np.asarray(jnp.mean(A, axis=0)),
                               atol=1e-5)
    X = _rand(24, 5, 8)
    Y = _rand(80, 5, 9)
    np.testing.assert_allclose(np.asarray(op.matmat(X)), np.asarray(Ac @ X),
                               atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(op.rmatmat(Y)), np.asarray(Ac.T @ Y),
                               atol=1e-3, rtol=1e-4)
    stacked = jnp.concatenate(list(op.row_panels(32)), axis=0)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(Ac), atol=1e-5)


def test_composed_op_rejects_3d_base():
    stack = jnp.zeros((3, 16, 8))
    with pytest.raises(ValueError, match="2-D base"):
        linalg.CenteredOp(linalg.StackedOp(stack))
    with pytest.raises(ValueError, match="2-D base"):
        linalg.pca(stack, 2)  # coerces to StackedOp -> CenteredOp must reject


def test_column_means_streams_host_panels():
    A = np.asarray(_rand(100, 12, 10)) + 1.5
    mu = linalg.column_means(linalg.HostOp(A, block_rows=30))
    np.testing.assert_allclose(np.asarray(mu), A.mean(axis=0), atol=1e-5)


def test_low_rank_update_op_and_deflation():
    A, sig = make_test_matrix(128, 48, "fast", seed=11)
    U = _rand(128, 4, 12)
    V = _rand(48, 4, 13)
    op = linalg.LowRankUpdateOp(linalg.DenseOp(A), U, V)
    dense = A + U @ V.T
    X = _rand(48, 6, 14)
    np.testing.assert_allclose(np.asarray(op.matmat(X)), np.asarray(dense @ X),
                               atol=1e-4, rtol=1e-4)
    stacked = jnp.concatenate(list(op.row_panels(50)), axis=0)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(dense), atol=1e-5)
    with pytest.raises(ValueError):
        linalg.LowRankUpdateOp(linalg.DenseOp(A), U, _rand(47, 4, 15))

    # deflation: after peeling the top-k subspace, the next leading singular
    # value is sigma_{k+1} of the original
    k = 8
    Uk, Sk, Vtk = linalg.svd(A, k, seed=0)
    resid = linalg.deflated(linalg.DenseOp(A), Uk, Sk, Vtk)
    S_next = linalg.svd(resid, 3, seed=1)[1]
    np.testing.assert_allclose(float(S_next[0]), float(sig[k]), rtol=5e-3)


# ---------------------------------------------------------------------------
# panel-wise residual
# ---------------------------------------------------------------------------

def test_residual_matches_low_rank_error_dense():
    A, _ = make_test_matrix(200, 64, "fast", seed=16)
    res = linalg.svd(A, 10, seed=2)
    want = float(low_rank_error(A, *res))
    got = float(linalg.residual(A, res))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # panelized accumulation only reorders the fp32 sums
    got_panels = float(linalg.residual(A, res, block_rows=37))
    np.testing.assert_allclose(got_panels, want, rtol=1e-4)


def test_residual_streams_host_source():
    A_host = np.asarray(make_test_matrix(300, 48, "fast", seed=17)[0])
    op = linalg.HostOp(A_host, block_rows=64)
    res = linalg.svd(op, 8, seed=3)
    want = float(low_rank_error(jnp.asarray(A_host), *res))
    got = float(linalg.residual(op, res))
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_residual_stacked_source():
    A = jnp.stack([make_test_matrix(64, 24, "fast", seed=20 + i)[0] for i in range(3)])
    res = linalg.svd(A, 5, seed=4)
    got = float(linalg.residual(A, res))
    # reference: per-slice errors combined into the stack-wide Frobenius ratio
    num = den = 0.0
    for i in range(3):
        e = float(low_rank_error(A[i], res[0][i], res[1][i], res[2][i]))
        w = float(jnp.sum(A[i] ** 2))
        num += (e ** 2) * w
        den += w
    np.testing.assert_allclose(got, np.sqrt(num / den), rtol=1e-5)


def test_residual_stacked_tolerates_zero_slice():
    """An all-zero slice (padded/ragged batch entry) must not NaN the
    stack-wide residual — the squared sums are combined BEFORE the divide."""
    A = jnp.stack([make_test_matrix(32, 12, "fast", seed=30)[0],
                   jnp.zeros((32, 12))])
    U = jnp.zeros((2, 32, 3))
    S = jnp.zeros((2, 3))
    Vt = jnp.zeros((2, 3, 12))
    got = float(linalg.residual(A, (U, S, Vt)))
    assert np.isfinite(got) and np.isclose(got, 1.0)  # zero factors -> err = 1


def _dot_precisions(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_dot_precisions(sub))
    return out


@pytest.mark.parametrize("caller", [None, "bfloat16"])
def test_entry_points_trace_f32_matmuls_at_highest(caller):
    """The facade traces its matmuls at full f32 precision (a TPU's default
    f32 matmul is one bf16 pass) unless the caller chose a precision."""
    A, _ = make_test_matrix(64, 16, "fast", seed=40)
    res = (jnp.ones((64, 2)), jnp.ones((2,)), jnp.ones((2, 16)))
    if caller is None:
        jaxpr = jax.make_jaxpr(lambda a: linalg.residual(a, res))(A)
        want = jax.lax.Precision.HIGHEST
    else:
        with jax.default_matmul_precision(caller):
            jaxpr = jax.make_jaxpr(lambda a: linalg.residual(a, res))(A)
        want = jax.lax.Precision.DEFAULT
    precisions = _dot_precisions(jaxpr.jaxpr)
    assert precisions and all(p == (want, want) for p in precisions), precisions
