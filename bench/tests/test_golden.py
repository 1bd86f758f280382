"""The default kind builds and judges exactly as the harness did before
kinds existed: at the rehearsal's tiny size (2048 x 256, pool 3, one seed)
its pool hashes to the digest the earlier `sources.Pool` gave, and its
judge returns, for one fixed factor set, the numbers the earlier
`reference.judge` returned, bit for bit.  The factor set is made by
numpy alone from a seeded generator, so that it is the same whatever
threads XLA's CPU backend uses.
"""
from __future__ import annotations

import hashlib

import jax
import numpy as np

from test_rehearsal import BENCH, TINY_CONFIG

import run  # noqa: E402

SEED = 2 ** 31 + 7
POOL_SHA256 = "7a97f237594aa049ce5c39942d5bb1759dd0daedfcb0329c33f0929f52944d6f"
JUDGED = {"residual_ratio": 236.53554787724212,
          "sigma_rel_err_top1": 0.0004900097846984863,
          "sigma_rel_err_top10": 0.0022679567337036133,
          "u_orth_err": 2.980232238769531e-07,
          "v_orth_err": 1.1920928955078125e-07}

dense_spectrum = run._module(BENCH / "kinds", "dense_spectrum", "kind")


def fixed_factors(sigma: np.ndarray, k: int = 20) -> tuple:
    rng = np.random.default_rng(5)
    m, n = TINY_CONFIG["m"], TINY_CONFIG["n"]
    U = np.linalg.qr(rng.standard_normal((m, k)))[0].astype(np.float32)
    Vt = np.linalg.qr(rng.standard_normal((n, k)))[0].T.astype(np.float32)
    S = (sigma[:k] * (1 + 1e-3 * rng.standard_normal(k))).astype(np.float32)
    return tuple(jax.numpy.asarray(x) for x in (U, S, Vt))


def test_pool_and_judge_read_as_before():
    pool = dense_spectrum.build_pool(TINY_CONFIG, {"pool": 3, "residency": "device"},
                                     SEED, jax.devices())
    digest = hashlib.sha256()
    for A in pool.members:
        digest.update(np.asarray(A).tobytes())
    assert digest.hexdigest() == POOL_SHA256
    assert dense_spectrum.judge(pool, 1, fixed_factors(pool.sigma), TINY_CONFIG) == JUDGED
    # the same matrices in host memory, put on the device for the judge
    host = dense_spectrum.build_pool(TINY_CONFIG, {"pool": 3, "residency": "host"},
                                     SEED, jax.devices())
    assert all(isinstance(A, np.ndarray) for A in host.members)
    assert dense_spectrum.judge(host, 1, fixed_factors(host.sigma), TINY_CONFIG) == JUDGED
