"""Quickstart: the paper's randomized k-SVD behind ONE call-site pattern.

`repro.linalg` takes an *operator source* — a device array, a host numpy
array, a 3-D stack, a sharded array, or a composed operator — plans an
execution (inspectable!), and runs the same Algorithm 1 numerics on the
path the source calls for.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, linalg
from repro.core import RSVDConfig, truncation_error
from repro.core.spectra import make_test_matrix

compile_cache.enable()

# A 1024 x 512 matrix with the paper's 'fast decay' spectrum (sigma_i = 1/i^2)
A, sigma = make_test_matrix(1024, 512, "fast", seed=0)
k = 32
opt = truncation_error(sigma, k)

# --- look before you leap: the planner's decision is an inspectable object
pl = linalg.plan(linalg.DenseOp(A), k)
print("plan     :", pl.describe())

# --- paper-faithful Algorithm 1 (Householder QR + LAPACK small SVD) --------
U, S, Vt = linalg.svd(A, k, overrides=RSVDConfig.faithful())
print(f"faithful : rank-{k} rel-error {linalg.residual(A, (U, S, Vt)):.3e}  (optimal {opt:.3e})")

# --- TPU fast path: CholeskyQR2 + Gram-Jacobi + fused one-pass kernels -----
# (the plan's fused_power flag is the EFFECTIVE decision: the VMEM budget
# gate can veto it, at which point the unfused body runs instead)
fast = linalg.plan(linalg.DenseOp(A), k, overrides=RSVDConfig.fast())
U, S, Vt = linalg.svd(A, k, plan=fast)
print(f"fast     : rank-{k} rel-error {linalg.residual(A, (U, S, Vt)):.3e}  ({fast.describe()})")

# --- eigenvalues-only mode (the paper's benchmark setting) -----------------
S_only = linalg.eigvals(A, 10, overrides=RSVDConfig.fast())
print("top-10 singular values:", [f"{float(s):.4f}" for s in S_only])
print("exact                 :", [f"{float(s):.4f}" for s in sigma[:10]])

# --- out-of-core: a host-resident matrix streams row panels ----------------
# HostOp keeps A on the host; only one block_rows x n panel is device-
# resident at a time, and the panel-wise residual never forms an m x n
# reconstruction either (DESIGN.md §3).
A_host = np.asarray(A)  # pretend this is bigger than device memory
host = linalg.HostOp(A_host, block_rows=256)
res = linalg.svd(host, k)
print(f"streamed : rank-{k} rel-error {linalg.residual(host, res):.3e}  "
      f"({linalg.plan(host, k).describe()})")

# --- batched: a fleet of small SVDs under one vmap -------------------------
stack = jnp.stack([make_test_matrix(256, 96, "fast", seed=i)[0] for i in range(8)])
Ub, Sb, Vtb = linalg.svd(stack, 10)  # [8, 256, 96] -> per-slice factors
print(f"batched  : stack rel-error {linalg.residual(stack, (Ub, Sb, Vtb)):.3e}")

# --- spec-driven: state the ACCURACY, let the engine find the rank ---------
# Tolerance(eps) grows the basis panel by panel (posterior error estimator,
# DESIGN.md §Specs) and stops as soon as the requested Frobenius error is
# certified — the plan records the full-rank fallback schedule, the result
# records the prefix that actually ran.
dec = linalg.decompose(A, linalg.Tolerance(1e-2))
print(f"tol 1e-2 : found rank {dec.rank} in {len(dec.rank_history)}/"
      f"{len(dec.plan.rank_schedule)} panels, rel-error "
      f"{linalg.residual(A, dec.factors):.3e}  ({dec.plan.describe()})")

# Other registry kinds ride the same spec machinery:
Q, B = linalg.decompose(A, linalg.Rank(k), kind="qb")        # basis only
print(f"qb       : Q {Q.shape} B {B.shape}  (A ~= Q @ B)")
pr, L, Umat, pc = linalg.decompose(A, linalg.Tolerance(2e-2), kind="lu")
print(f"lu       : L {L.shape} U {Umat.shape}  (A[pr][:, pc] ~= L @ U)")

# --- composed operators: the new workload class ----------------------------
# PCA without materializing the centered matrix ...
pca_res = linalg.pca(A, 8)
print("pca      : top-8 explained variance",
      [f"{float(v):.4f}" for v in pca_res.explained_variance[:3]], "...")
# ... or with the variance stated instead of the count:
pca_e = linalg.pca(A, linalg.Energy(0.99))
print(f"pca      : Energy(0.99) kept {pca_e.components.shape[0]} components")
# ... and deflation A - U_k S_k V_k^T as an operator: the next solve sees
# the residual spectrum (sigma_{k+1} and below) without forming it.
defl = linalg.deflated(linalg.DenseOp(A), U, S, Vt)
S_next = linalg.svd(defl, 5)[1]
print(f"deflated : leading residual sigma {float(S_next[0]):.4e}"
      f"  (exact sigma_{k + 1} = {float(sigma[k]):.4e})")
