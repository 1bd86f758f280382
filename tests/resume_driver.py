"""Kill -9 resume drivers (run by tests/test_snapshot.py, slow lane).

Each mode spawns a CHILD copy of this script that runs a checkpointed
solve under a `Checkpointer` subclass that SIGKILLs its own process right
after the N-th completed (durable) save — a real, unhandled process death
mid-solve: no Python cleanup, no atexit, no flushing.  The parent then
verifies the durability contract on the survivors:

  streamed   the streamed stage machine (core/blocked.py) resumes from
             the surviving snapshots to factors BIT-identical to an
             uninterrupted run at the same seed;
  adaptive   same for the adaptive growth loop (core/adaptive.py) behind
             `linalg.decompose(A, Tolerance(...), checkpoint=...)`;
  service    the decomposition service dies mid-solve; the write-ahead
             job record survives, `DecompositionService.restore(dir)`
             re-enqueues the job, and its future resolves bit-identical
             to an uninterrupted reference — with the job store drained;
  ckpt       repro.checkpoint's `CheckpointManager` is killed with an
             async save in flight and `.tmp` debris on disk: the previous
             step stays loadable and no debris is ever picked up.

Sentinels ("RESUME_STREAMED_OK", ...) are printed only after every
assertion passed; the pytest wrappers assert on them plus returncode 0.
"""
import os
import pathlib
import signal
import subprocess
import sys
import time

import numpy as np

M, N, BLOCK, RANK, SEED = 2048, 128, 128, 8, 5
ADAPTIVE_SHAPE = (160, 64)
KILL_AFTER_SAVES = 2


def _decay(m, n, seed=0):
    rng = np.random.default_rng(seed)
    U, _ = np.linalg.qr(rng.standard_normal((m, n)))
    V, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.exp(-np.arange(n) / 6.0)
    return (U @ (s[:, None] * V.T)).astype(np.float32)


def _same(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _kill_after(directory, saves):
    """A Checkpointer that SIGKILLs its own process right AFTER the given
    number of completed saves: the snapshots are published (renamed and
    parent-fsynced) before death, the in-flight solve is not."""
    from repro.linalg import snapshot as snap

    class KillAfter(snap.Checkpointer):
        def save_now(self, step, capture):
            path = super().save_now(step, capture)
            if self.saves >= saves:
                os.kill(os.getpid(), signal.SIGKILL)
            return path

    return KillAfter(directory, every=1, keep_last=2)


def _spawn_child(mode, workdir):
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), mode + "_child", workdir],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})  # never the chip
    if proc.returncode != -signal.SIGKILL:
        raise SystemExit(
            f"child should have died by SIGKILL, got rc={proc.returncode}\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")


# ---------------------------------------------------------------------------
# streamed engine
# ---------------------------------------------------------------------------

def _streamed_solve(A, ck=None):
    from repro.core import blocked
    from repro.core.rsvd import RSVDConfig
    from repro.linalg import snapshot as snap

    cfg = RSVDConfig(qr_method="cqr2", power_iters=2, block_rows=BLOCK)
    ctl = None if ck is None else snap.RunControl(checkpointer=ck)
    with snap.maybe_scope(ctl):
        return blocked.svd_streamed(A, RANK, cfg, seed=SEED)


def streamed_child(workdir):
    A = _decay(M, N)
    ck = _kill_after(pathlib.Path(workdir) / "ck", KILL_AFTER_SAVES)
    _streamed_solve(A, ck)
    raise SystemExit("streamed solve finished before the kill fired")


def run_streamed(workdir):
    _spawn_child("streamed", workdir)
    from repro.linalg import snapshot as snap

    A = _decay(M, N)
    ref = _streamed_solve(A)
    ckdir = pathlib.Path(workdir) / "ck"
    survivors = [p for p in ckdir.glob("snap_*") if p.suffix != ".tmp"]
    assert survivors, "no durable snapshot survived the SIGKILL"
    out = _streamed_solve(A, snap.Checkpointer(ckdir))
    _same(ref, out)
    print("RESUME_STREAMED_OK")


# ---------------------------------------------------------------------------
# adaptive engine
# ---------------------------------------------------------------------------

def _adaptive_solve(checkpoint=None):
    import jax.numpy as jnp
    from repro import linalg

    A = jnp.asarray(_decay(*ADAPTIVE_SHAPE, seed=1))
    return linalg.decompose(A, linalg.Tolerance(1e-3, panel=8, max_rank=48),
                            seed=3, checkpoint=checkpoint)


def adaptive_child(workdir):
    ck = _kill_after(pathlib.Path(workdir) / "ck", KILL_AFTER_SAVES)
    _adaptive_solve(checkpoint=ck)
    raise SystemExit("adaptive solve finished before the kill fired")


def run_adaptive(workdir):
    _spawn_child("adaptive", workdir)
    ref = _adaptive_solve()
    out = _adaptive_solve(checkpoint=str(pathlib.Path(workdir) / "ck"))
    _same(ref.factors, out.factors)
    assert out.rank == ref.rank
    assert out.rank_history == ref.rank_history
    print("RESUME_ADAPTIVE_OK")


# ---------------------------------------------------------------------------
# service crash + restore
# ---------------------------------------------------------------------------

def service_child(workdir):
    from repro import linalg
    from repro.serve.decomp import DecompositionService

    wd = pathlib.Path(workdir)
    arr = _decay(M, N, seed=2)
    svc = DecompositionService(jobstore=str(wd / "store"))
    fut = svc.submit(linalg.HostOp(arr, block_rows=BLOCK), linalg.Rank(RANK),
                     seed=SEED, checkpoint=str(wd / "ck"))
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline and not fut.done():
        durable = [p for p in (wd / "ck").glob("snap_*") if p.suffix != ".tmp"]
        if len(durable) >= KILL_AFTER_SAVES:
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(0.002)
    raise SystemExit("service solve finished (or stalled) before the kill")


def run_service(workdir):
    _spawn_child("service", workdir)
    from repro import linalg
    from repro.serve.decomp import DecompositionService
    from repro.serve.decomp.jobstore import JobStore

    wd = pathlib.Path(workdir)
    arr = _decay(M, N, seed=2)
    ref = linalg.decompose(linalg.HostOp(arr, block_rows=BLOCK),
                           linalg.Rank(RANK), seed=SEED)
    svc = DecompositionService.restore(str(wd / "store"))
    try:
        assert len(svc.restored_futures) == 1, sorted(svc.restored_futures)
        dec = next(iter(svc.restored_futures.values())).result(timeout=300)
        assert svc.metrics.export()["resumed_jobs"] == 1
    finally:
        svc.close()
    _same(ref.factors, dec.factors)
    assert JobStore(wd / "store").pending() == []
    print("SERVICE_RESTORE_OK")


# ---------------------------------------------------------------------------
# repro.checkpoint crash-mid-save
# ---------------------------------------------------------------------------

def _ckpt_tree():
    import jax.numpy as jnp

    return {"w": jnp.arange(12.0).reshape(3, 4)}


def ckpt_child(workdir):
    from repro.checkpoint.checkpoint import CheckpointManager

    wd = pathlib.Path(workdir)
    mgr = CheckpointManager(str(wd), keep_last=3)
    mgr.save(1, _ckpt_tree(), blocking=True)      # the durable previous step
    debris = wd / "step_00000007.tmp"             # a crash mid-publish...
    debris.mkdir()
    (debris / "shard_0.npz").write_bytes(b"partial bytes, never renamed")
    mgr.save(2, _ckpt_tree(), blocking=False)     # ...and an async save
    os.kill(os.getpid(), signal.SIGKILL)          # in flight when we die


def run_ckpt(workdir):
    _spawn_child("ckpt", workdir)
    import jax.numpy as jnp
    from repro.checkpoint.checkpoint import CheckpointManager

    mgr = CheckpointManager(workdir)
    steps = mgr.all_steps()
    # step 1 is durable; step 2 may or may not have completed before the
    # kill; the .tmp debris must never appear either way
    assert 1 in steps and set(steps) <= {1, 2}, steps
    assert 7 not in steps
    restored, step = mgr.restore({"w": jnp.zeros((3, 4))})
    assert step == max(steps)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(12.0).reshape(3, 4))
    print("CKPT_CRASH_OK")


# ---------------------------------------------------------------------------

MODES = {
    "streamed": run_streamed, "streamed_child": streamed_child,
    "adaptive": run_adaptive, "adaptive_child": adaptive_child,
    "service": run_service, "service_child": service_child,
    "ckpt": run_ckpt, "ckpt_child": ckpt_child,
}


def main():
    mode, workdir = sys.argv[1], sys.argv[2]
    pathlib.Path(workdir).mkdir(parents=True, exist_ok=True)
    MODES[mode](workdir)


if __name__ == "__main__":
    main()
