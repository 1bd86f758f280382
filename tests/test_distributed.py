"""Distributed (shard_map) RSVD == single-device RSVD, via 8-device subprocess."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run_driver(name: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)  # driver sets its own device count
    env["JAX_PLATFORMS"] = "cpu"  # a child never takes the chip
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / name)],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_distributed_rsvd_matches_reference():
    out = _run_driver("distributed_driver.py")
    assert "DISTRIBUTED_RSVD_OK" in out


@pytest.mark.slow
def test_elastic_reshard_on_load():
    """Checkpoint on mesh (8,) -> restore + continue on mesh (2,4)."""
    out = _run_driver("elastic_driver.py")
    assert "ELASTIC_OK" in out


def test_distributed_rsvd_inprocess_multidevice():
    """shard_map RSVD == dense RSVD on the ambient devices (no subprocess).

    Runs whenever the interpreter already sees >1 CPU device — the CI tier-1
    job sets XLA_FLAGS=--xla_force_host_platform_device_count=4 precisely so
    this path is exercised on every push; single-device local runs skip it.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (CI sets xla_force_host_platform_device_count)")

    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import linalg
    from repro.core import RSVDConfig, low_rank_error, truncation_error
    from repro.core.spectra import make_test_matrix

    n_dev = len(jax.devices())
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    A, sig = make_test_matrix(32 * n_dev, 64, "fast", seed=0)
    A_sharded = jax.device_put(A, NamedSharding(mesh, P("data", None)))

    k = 8
    op = linalg.ShardedOp(A_sharded, mesh, "data")
    assert linalg.plan(op, k).path == "sharded"
    U, S, Vt = linalg.svd(op, k, overrides=RSVDConfig(power_iters=1))
    err = float(low_rank_error(A, jnp.asarray(U), jnp.asarray(S), jnp.asarray(Vt)))
    opt = float(truncation_error(sig, k))
    assert err <= 1.10 * opt + 1e-6, (err, opt)
    S_dense = jnp.linalg.svd(A, compute_uv=False)[:k]
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_dense), rtol=5e-3)


def test_straggler_watchdog_flags_slow_steps():
    from repro.train.trainer import Trainer, TrainerConfig
    from repro.optim import adamw
    from repro.configs import get_config

    cfg = get_config("llama3.2-1b").reduced()
    tr = Trainer(cfg, adamw.AdamWConfig(), TrainerConfig(straggler_factor=3.0),
                 step_fn=lambda *a: a)
    # steady 100ms steps, then a 10x straggler
    flags = [tr._watchdog(0.1, s) for s in range(10)]
    assert not any(flags)
    assert tr._watchdog(1.0, 10) is True
    assert tr.straggler.flagged_steps == 1
    assert tr.straggler.worst_ratio > 5
