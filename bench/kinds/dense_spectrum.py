"""Dense float32 matrices of known spectrum: the paper's Sec. 4 test matrices.

The kind of every configuration that names none.  Each pool member is
A = U diag(sigma) V^T, m x n, with U and V orthonormal and sigma one of the
paper's three spectra (arXiv:2110.03423 Sec. 4), named by the
configuration's "spectrum":

- "inverse_square", (i) fast decay: sigma_i = 1/i^2;
- "sharp", (ii) sharp decay: sigma_i = 1e-4 + 1/(1 + exp(i + 1 - beta)),
  beta the configuration's "beta", 50 where it gives none;
- "slow", (iii) slow decay: sigma_i = 1/i^0.1.

On one chip U and V are the Q factors of Gaussian matrices; member 0 is
the base and member j > 0 the base with its rows permuted and sign-flipped,
which keeps the singular values exactly.  Row-sharded over the cell's chips
("placement": "rows", m >= n), each member has a U of its own, G R1^-1
R2^-1 by CholeskyQR2 of a Gaussian G that each chip makes on its own rows,
the n x n Grams the only exchange; V is shared.  Either way the whole pool
comes out of one jitted call.

A solve is judged against the exact spectrum (`judge`), and priced from the
configuration's sizes alone (`required_work`).

Everything here is the benchmark's own: it imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
import sources

SPECTRA = ("inverse_square", "sharp", "slow")
DEFAULT_BETA = 50.0
HIGHEST = jax.lax.Precision.HIGHEST


def _sigma(xp, name: str, r: int, beta: float, dtype):
    i = xp.arange(1, r + 1, dtype=dtype)
    if name == "inverse_square":
        return 1.0 / i ** 2
    if name == "sharp":
        return 1e-4 + 1.0 / (1.0 + xp.exp(i + 1.0 - beta))
    return 1.0 / i ** 0.1


def spectrum(name: str, r: int, beta: float = DEFAULT_BETA) -> np.ndarray:
    """sigma_1 .. sigma_r of the named spectrum, in float64."""
    with np.errstate(over="ignore"):   # sharp: exp overflows, 1/inf is 0
        return _sigma(np, name, r, beta, np.float64)


def optimal_ratio(sigma: np.ndarray, k: int) -> float:
    """||A - A_k||_F / ||A||_F from the exact spectrum."""
    sq = np.asarray(sigma, np.float64) ** 2
    return float(np.sqrt(sq[k:].sum() / sq.sum()))


def min_rank(sigma: np.ndarray, eps: float) -> int:
    """The smallest rank r >= 1 whose optimal relative error
    ||A - A_r||_F / ||A||_F meets eps."""
    sq = np.asarray(sigma, np.float64) ** 2
    tail = np.append(np.cumsum(sq[::-1])[::-1], 0.0)   # tail[r] = sum_{i>r}
    return max(1, int(np.nonzero(np.sqrt(tail / sq.sum()) <= eps)[0][0]))


def _orthonormal(key, rows: int, cols: int):
    Q, R = jnp.linalg.qr(jax.random.normal(key, (rows, cols), jnp.float32))
    return Q * jnp.sign(jnp.diagonal(R))[None, :]


@functools.partial(jax.jit, static_argnames=("m", "n", "pool", "spectrum", "beta"))
def build(seed, *, m: int, n: int, pool: int, spectrum: str = "inverse_square",
          beta: float = DEFAULT_BETA):
    """The pool's `pool` matrices, m x n f32, from the seed's words."""
    key = sources.seed_key(seed)
    r = min(m, n)
    U = _orthonormal(jax.random.fold_in(key, 0), m, r)
    V = _orthonormal(jax.random.fold_in(key, 1), n, r)
    sig = _sigma(jnp, spectrum, r, beta, jnp.float32)
    base = jnp.matmul(U * sig[None, :], V.T, precision=HIGHEST)
    members = [base]
    for j in range(1, pool):
        k_perm, k_sign = jax.random.split(jax.random.fold_in(key, 2 + j))
        perm = jax.random.permutation(k_perm, m)
        signs = jax.random.rademacher(k_sign, (m,), jnp.float32)
        members.append(base[perm] * signs[:, None])
    return tuple(members)


def _r_inverse(G):
    """R^-1 of the CholeskyQR step G = Q R, G's rows sharded or not."""
    R = jnp.linalg.cholesky(jnp.matmul(G.T, G, precision=HIGHEST)).T
    eye = jnp.eye(R.shape[0], dtype=R.dtype)
    return jax.lax.linalg.triangular_solve(R, eye, left_side=False, lower=False)


@functools.partial(jax.jit, static_argnames=("m", "n", "pool", "spectrum", "beta", "rows"))
def build_rows(seed, *, m: int, n: int, pool: int, spectrum: str, beta: float, rows):
    """The pool's matrices, m x n f32 (m >= n), laid out as `rows` (a row
    sharding): member j is G1 R2^-1 diag(sigma) V^T with G1 = G R1^-1,
    CholeskyQR2 of its own Gaussian G, made on each chip's own rows."""
    key = sources.seed_key(seed)
    V = _orthonormal(jax.random.fold_in(key, 1), n, n)
    svt = _sigma(jnp, spectrum, n, beta, jnp.float32)[:, None] * V.T
    members = []
    for j in range(pool):
        G = jax.lax.with_sharding_constraint(
            jax.random.normal(jax.random.fold_in(key, 2 + j), (m, n), jnp.float32), rows)
        G1 = jnp.matmul(G, _r_inverse(G), precision=HIGHEST)
        A = jnp.matmul(G1, jnp.matmul(_r_inverse(G1), svt, precision=HIGHEST),
                       precision=HIGHEST)
        members.append(jax.lax.with_sharding_constraint(A, rows))
    return tuple(members)


class Pool:
    """The cell's matrices: `members[j]` is what call j passes to the
    program (a device array, a row-sharded one, or a numpy array),
    `device_member(j)` the same matrix on the device for the judge,
    `sharding` the (mesh, axis) of a row-sharded pool or None, and `sigma`
    the exact spectrum every member shares."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        if cfg["dtype"] != "float32":
            raise ValueError(f"configuration {cfg['name']}: dtype {cfg['dtype']!r} "
                             "is not supported; dense_spectrum makes float32 only")
        name = cfg["spectrum"]
        if name not in SPECTRA:
            raise ValueError(f"configuration {cfg['name']}: spectrum {name!r} is "
                             f"not one of {SPECTRA}")
        if "beta" in cfg and name != "sharp":
            raise ValueError(f"configuration {cfg['name']}: beta shapes the sharp "
                             f"spectrum only, not {name!r}")
        m, n, size = cfg["m"], cfg["n"], traffic["pool"]
        beta = float(cfg.get("beta", DEFAULT_BETA))
        self.sigma = spectrum(name, min(m, n), beta)
        self.device = devices[0]
        self.sharding = None
        self._on_device = (None, None)
        words = sources.seed_words(seed)
        if cfg.get("placement") == "rows":
            if m < n or m % len(devices):
                raise ValueError(f"configuration {cfg['name']}: a row-sharded pool "
                                 f"needs m >= n and m a multiple of {len(devices)} chips")
            mesh = sources.row_mesh(devices)
            self.sharding = (mesh, sources.ROWS_AXIS)
            members = build_rows(words, m=m, n=n, pool=size, spectrum=name, beta=beta,
                                 rows=jax.sharding.NamedSharding(
                                     mesh, jax.sharding.PartitionSpec(sources.ROWS_AXIS, None)))
        else:
            with jax.default_device(self.device):
                members = build(words, m=m, n=n, pool=size, spectrum=name, beta=beta)
        if traffic["residency"] == "host":
            members = [np.asarray(A) for A in jax.device_get(members)]
        self.members = list(jax.block_until_ready(members))

    def device_member(self, j: int):
        """Member j on the device; a host member is put there once for
        consecutive calls with the same j."""
        A = self.members[j]
        if not isinstance(A, np.ndarray):
            return A
        if self._on_device[0] != j:
            self._on_device = (j, jax.device_put(A, self.device))
        return self._on_device[1]


def build_pool(cfg: dict, traffic: dict, seed: int, devices) -> Pool:
    return Pool(cfg, traffic, seed, devices)


def numbers(cfg: dict) -> tuple:
    """The names `judge` returns for the configuration's spec."""
    head = (("residual_ratio",) if sources.spec(cfg)["kind"] == "rank"
            else ("residual_over_eps", "rank_over_min"))
    return head + ("sigma_rel_err_top1", "sigma_rel_err_top10", "u_orth_err", "v_orth_err")


def judge(pool: Pool, member: int, factors, cfg: dict) -> dict:
    """The numbers of one solve of pool member `member`, all "lower is
    better"; a shape or a value that is not finite reads as inf.

    - residual_ratio (a rank-k spec): ||A - U S Vt||_F / ||A||_F over the
      optimal sqrt(sum_{i>k} sigma_i^2 / sum_i sigma_i^2);
    - residual_over_eps, rank_over_min (a tolerance spec, any rank r
      returned): the achieved ||A - U S Vt||_F / ||A||_F over eps, and r
      over the smallest rank whose optimal error meets eps;
    - sigma_rel_err_top1, sigma_rel_err_top10: the largest relative error
      of the leading 1 and 10 singular values against the exact spectrum;
    - u_orth_err, v_orth_err: max |U^T U - I| and max |Vt Vt^T - I|.
    """
    spec = sources.spec(cfg)
    A = pool.device_member(member)
    U, S, Vt = factors
    m, n = A.shape
    r = S.shape[0] if S.ndim == 1 else 0
    rank_ok = r == spec["k"] if spec["kind"] == "rank" else 1 <= r <= min(m, n)
    if not rank_ok or U.shape != (m, r) or Vt.shape != (r, n):
        return {name: float("inf") for name in numbers(cfg)}
    sums = reference.residual_sums(A, factors, pool.sharding)
    orth = reference.orth_errors(U, Vt)
    s = np.asarray(jax.device_get(jnp.asarray(S, jnp.float32)), np.float64)
    sigma = pool.sigma
    rel = np.abs(s - sigma[:r]) / sigma[:r]
    residual = np.sqrt(sums[0] / sums[1])
    if spec["kind"] == "rank":
        out = {"residual_ratio": float(residual / optimal_ratio(sigma, r))}
    else:
        out = {"residual_over_eps": float(residual / spec["eps"]),
               "rank_over_min": r / min_rank(sigma, spec["eps"])}
    out.update({
        "sigma_rel_err_top1": float(rel[0]),
        "sigma_rel_err_top10": float(rel[:10].max()),
        "u_orth_err": float(orth[0]),
        "v_orth_err": float(orth[1]),
    })
    return {name: (v if np.isfinite(v) else float("inf")) for name, v in out.items()}


def required_work(cfg: dict) -> dict:
    """FLOPs and bytes of one solve, from the configuration's sizes alone
    (m, n, s = k + oversample, q power iterations; k the spec's rank, or
    under a tolerance the smallest rank that meets it), never from the plan
    the program picks, so that a change which fuses, unfuses or reorders
    the solve leaves this yardstick where it was:

    - FLOPs: Algorithm 1 multiplies A by an n x s or m x s block 2q + 2
      times (the sketch, two products per power iteration, the
      projection), each 2 m n s operations, fused or not.
    - Bytes: q + 1 reads of A, the fewest that any known formulation needs
      (each power iteration's two products share one read of A, and the
      sketch and the projection ride the first and the last).
    """
    m, n = cfg["m"], cfg["n"]
    spec = sources.spec(cfg)
    k = spec["k"] if spec["kind"] == "rank" else min_rank(
        spectrum(cfg["spectrum"], min(m, n), float(cfg.get("beta", DEFAULT_BETA))),
        spec["eps"])
    s = k + cfg["oversample"]
    q = cfg["power_iters"]
    itemsize = {"float32": 4}[cfg["dtype"]]
    return {
        "flops": (2 * q + 2) * 2 * m * n * s,
        "bytes": (q + 1) * m * n * itemsize,
    }
