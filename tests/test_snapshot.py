"""Resumable decompositions: panel-granular checkpoint/resume, deadlines,
cancellation, crash-safe service restart (linalg/snapshot.py and friends).

Pins the subsystem's contracts:

  * `Checkpointer` publishes atomically (tmp -> fsync -> rename -> parent
    fsync): `.tmp` debris and manifest-less directories are invisible to
    `steps()`/`latest()`, `keep_last` GC holds, the cadence (`every`) is
    honored and `save_now` ignores it;
  * a streamed solve interrupted at EVERY panel-group boundary (injected
    `preempt`) resumes to factors BIT-identical to the uninterrupted run
    at the same seed — same for the adaptive Tolerance solve under
    `device_lost`, including rank/rank_history;
  * a stale snapshot whose token mismatches (different seed/config) is
    silently ignored — the run is fresh, never poisoned;
  * checkpointing an UNINTERRUPTED run changes nothing: factors stay
    bit-identical with saves on (host-side writes only);
  * cancellation and deadlines are cooperative: observed at panel-group
    boundaries, raising `Cancelled`/`DeadlineExceeded` carrying the final
    snapshot path, and the parked solve resumes bit-identically;
  * the guard absorbs TRANSIENT_ERRORS by restarting the SAME rung (ambient
    checkpointer preserves progress, `RungReport.restarts` counts it); an
    exhausted restart budget raises (report mode) or climbs the ladder
    (retry mode);
  * the service honors `deadline_s` (queued lapse resolves without running)
    and `Future.cancel()` (queued AND running), restores write-ahead jobs
    after a crash bit-identically, and exports the resilience counters;
  * kill -9 subprocess drivers (tests/resume_driver.py, slow lane) prove
    all of the above against a real unhandled process death.
"""
import pathlib
import subprocess
import sys
import threading
import time
from concurrent.futures import CancelledError

import jax.numpy as jnp
import numpy as np
import pytest

from repro import linalg
from repro.core import blocked
from repro.core.rsvd import RSVDConfig
from repro.linalg import faults, guard
from repro.linalg import registry as registry_mod
from repro.linalg import snapshot as snap
from repro.serve.decomp import DecompositionService
from repro.serve.decomp.jobstore import JobStore
from repro.serve.decomp.metrics import MetricsRecorder

ROOT = pathlib.Path(__file__).resolve().parent.parent

import os  # noqa: E402  (os.environ for the subprocess drivers)


def _decay(m, n, seed=0):
    rng = np.random.default_rng(seed)
    U, _ = np.linalg.qr(rng.standard_normal((m, n)))
    V, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.exp(-np.arange(n) / 6.0)
    return (U @ (s[:, None] * V.T)).astype(np.float32)


def _same(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _capture():
    return {"x": np.arange(6.0)}, {"token": "tok", "cursor": 3}


# ---------------------------------------------------------------------------
# Checkpointer
# ---------------------------------------------------------------------------

class TestCheckpointer:
    def test_atomic_layout_gc_and_latest(self, tmp_path):
        ck = snap.Checkpointer(tmp_path, every=1, keep_last=2)
        for s in (1, 2, 3, 4):
            ck.maybe_save(s, _capture)
        assert ck.steps() == [3, 4]          # keep_last GC
        assert ck.saves == 4
        assert ck.overhead_s > 0.0
        ref, arrays, meta = ck.latest("tok")
        assert (ref.step, ref.token) == (4, "tok")
        assert pathlib.Path(ref.path).name == "snap_00000004"
        np.testing.assert_array_equal(arrays["x"], np.arange(6.0))
        assert meta["cursor"] == 3
        assert ck.latest("other-token") is None   # stale plan -> fresh run

    def test_tmp_debris_and_manifestless_dirs_invisible(self, tmp_path):
        ck = snap.Checkpointer(tmp_path)
        ck.save_now(1, _capture)
        (tmp_path / "snap_00000009.tmp").mkdir()
        (tmp_path / "snap_00000009.tmp" / "state.npz").write_bytes(b"junk")
        (tmp_path / "snap_00000050").mkdir()      # renamed but manifest-less
        assert ck.steps() == [1]
        ref, _, _ = ck.latest("tok")
        assert ref.step == 1

    def test_cadence_and_save_now(self, tmp_path):
        ck = snap.Checkpointer(tmp_path, every=3, keep_last=10)
        for s in range(1, 7):
            ck.maybe_save(s, _capture)
        assert ck.steps() == [3, 6]               # every 3rd boundary
        ck.save_now(7, _capture)                  # cadence-exempt final save
        assert ck.steps() == [3, 6, 7]

    def test_bad_cadence_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="cadence"):
            snap.Checkpointer(tmp_path, every=0)

    def test_boundary_is_inert_without_scope(self):
        def explode():
            raise AssertionError("capture must not run with nothing in scope")
        snap.boundary(1, explode)                 # no control, no faults: no-op

    def test_boundary_cancel_and_deadline_save_final_snapshot(self, tmp_path):
        ev = threading.Event()
        ev.set()
        ck = snap.Checkpointer(tmp_path / "c")
        with snap.scope(snap.RunControl(checkpointer=ck, cancel_event=ev)):
            with pytest.raises(snap.Cancelled) as ei:
                snap.boundary(5, _capture)
        assert ei.value.snapshot_path.endswith("snap_00000005")
        assert pathlib.Path(ei.value.snapshot_path).is_dir()

        ctl = snap.RunControl(checkpointer=snap.Checkpointer(tmp_path / "d"),
                              deadline_t=time.monotonic() - 1.0)
        with snap.scope(ctl):
            with pytest.raises(snap.DeadlineExceeded) as ei:
                snap.boundary(2, _capture)
        assert ei.value.snapshot_path.endswith("snap_00000002")

        # without a checkpointer the verdicts still fire, path-less
        with snap.scope(snap.RunControl(cancel_event=ev)):
            with pytest.raises(snap.Cancelled) as ei:
                snap.boundary(1, _capture)
        assert ei.value.snapshot_path is None


# ---------------------------------------------------------------------------
# engine resume bit-identity (every boundary)
# ---------------------------------------------------------------------------

def _streamed_solve(A, ck=None):
    cfg = RSVDConfig(qr_method="cqr2", power_iters=2, block_rows=32)
    ctl = None if ck is None else snap.RunControl(checkpointer=ck)
    with snap.maybe_scope(ctl):
        return blocked.svd_streamed(A, 8, cfg, seed=7)


ADAPTIVE_SPEC = linalg.Tolerance(1e-3, panel=8, max_rank=48)


class TestResumeBitIdentity:
    def test_streamed_every_boundary(self, tmp_path):
        A = jnp.asarray(_decay(96, 40))
        ref = _streamed_solve(A)
        interrupted = 0
        for b in range(1, 100):
            ck = snap.Checkpointer(tmp_path / f"b{b:02d}")
            try:
                with faults.inject("preempt", panel=b):
                    _streamed_solve(A, ck)
            except faults.PreemptionError:
                interrupted += 1
                _same(ref, _streamed_solve(A, ck))
            else:
                break       # boundary b never fired: the solve has < b ticks
        # 3 panels x (sketch + 2x2 power passes + project) = 18 boundaries
        assert interrupted == 18

    def test_adaptive_every_boundary(self, tmp_path):
        A = jnp.asarray(_decay(120, 60, seed=1))
        ref = linalg.decompose(A, ADAPTIVE_SPEC, seed=3)
        interrupted = 0
        for b in range(1, 50):
            ckdir = str(tmp_path / f"b{b:02d}")
            try:
                with faults.inject("device_lost", panel=b):
                    linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ckdir)
            except faults.DeviceLostError:
                interrupted += 1
                out = linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ckdir)
                _same(ref.factors, out.factors)
                assert out.rank == ref.rank
                assert out.rank_history == ref.rank_history
                assert out.err_history == ref.err_history
            else:
                break
        assert interrupted >= 2   # >= 3 growth steps at this decay/tolerance

    def test_checkpointing_uninterrupted_run_changes_nothing(self, tmp_path):
        A = jnp.asarray(_decay(120, 60, seed=1))
        ref = linalg.decompose(A, ADAPTIVE_SPEC, seed=3)
        ck = snap.Checkpointer(tmp_path / "ck")
        out = linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ck)
        _same(ref.factors, out.factors)
        assert ck.saves > 0       # snapshots were actually written

    def test_stale_token_yields_fresh_run(self, tmp_path):
        A = jnp.asarray(_decay(120, 60, seed=1))
        ckdir = str(tmp_path / "ck")
        with pytest.raises(faults.DeviceLostError):
            with faults.inject("device_lost", panel=2):
                linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ckdir)
        # resume with a DIFFERENT seed: the surviving seed=3 snapshot's token
        # mismatches, so the run is fresh — identical to a never-interrupted
        # seed=4 solve, not a hybrid
        ref4 = linalg.decompose(A, ADAPTIVE_SPEC, seed=4)
        out4 = linalg.decompose(A, ADAPTIVE_SPEC, seed=4, checkpoint=ckdir)
        _same(ref4.factors, out4.factors)


# ---------------------------------------------------------------------------
# cooperative cancellation / deadlines at the linalg facade
# ---------------------------------------------------------------------------

class TestCancelAndDeadline:
    def test_cancel_mid_solve_parks_then_resumes(self, tmp_path):
        A = jnp.asarray(_decay(120, 60, seed=1))
        ref = linalg.decompose(A, ADAPTIVE_SPEC, seed=3)
        ev = threading.Event()
        ev.set()
        ctl = snap.RunControl(checkpointer=snap.Checkpointer(tmp_path / "c"),
                              cancel_event=ev)
        with pytest.raises(snap.Cancelled) as ei:
            linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ctl)
        assert pathlib.Path(ei.value.snapshot_path).is_dir()
        ev.clear()
        out = linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ctl)
        _same(ref.factors, out.factors)

    def test_deadline_mid_solve_parks_then_resumes(self, tmp_path):
        A = jnp.asarray(_decay(120, 60, seed=1))
        ref = linalg.decompose(A, ADAPTIVE_SPEC, seed=3)
        ctl = snap.RunControl(checkpointer=snap.Checkpointer(tmp_path / "d"),
                              deadline_t=time.monotonic() - 1.0)
        with pytest.raises(snap.DeadlineExceeded) as ei:
            linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ctl)
        assert pathlib.Path(ei.value.snapshot_path).is_dir()
        ctl.deadline_t = None
        out = linalg.decompose(A, ADAPTIVE_SPEC, seed=3, checkpoint=ctl)
        _same(ref.factors, out.factors)


# ---------------------------------------------------------------------------
# guard: transient restarts
# ---------------------------------------------------------------------------

def _host_op(seed=2):
    return linalg.HostOp(_decay(256, 64, seed=seed), block_rows=64)


class TestGuardRestarts:
    def test_transient_absorbed_same_rung_bit_identical(self, tmp_path):
        ref = linalg.decompose(_host_op(), linalg.Rank(8), seed=5, guard="retry")
        with faults.inject("preempt", panel=4):
            dec = linalg.decompose(_host_op(), linalg.Rank(8), seed=5,
                                   guard="retry", checkpoint=str(tmp_path / "g"))
        assert dec.health.ok
        assert sum(a.restarts for a in dec.health.attempts) == 1
        assert "restarts=1" in dec.health.describe()
        _same(ref.factors, dec.factors)

    def test_exhausted_budget_raises_in_report_mode(self, tmp_path):
        policy = guard.GuardPolicy(mode="report", max_restarts=1)
        with faults.inject("device_lost", panel=1, times=10):
            with pytest.raises(faults.DeviceLostError):
                linalg.decompose(_host_op(), linalg.Rank(8), seed=5,
                                 guard=policy, checkpoint=str(tmp_path / "g"))

    def test_exhausted_budget_climbs_ladder_in_retry_mode(self):
        policy = guard.GuardPolicy(mode="retry", max_restarts=0)
        with faults.inject("preempt", panel=1, times=1):
            dec = linalg.decompose(_host_op(), linalg.Rank(8), seed=5,
                                   guard=policy)
        assert dec.health.ok                       # the next rung succeeded
        assert not dec.health.attempts[0].healthy
        assert "PreemptionError" in dec.health.attempts[0].error

    def test_cancel_never_absorbed_by_guard(self, tmp_path):
        ev = threading.Event()
        ev.set()
        ctl = snap.RunControl(cancel_event=ev)
        with pytest.raises(snap.Cancelled):
            linalg.decompose(_host_op(), linalg.Rank(8), seed=5,
                             guard="retry", checkpoint=ctl)

    def test_policy_validates_restart_fields(self):
        with pytest.raises(ValueError):
            guard.GuardPolicy(max_restarts=-1)
        with pytest.raises(ValueError):
            guard.GuardPolicy(restart_backoff_s=-0.5)


# ---------------------------------------------------------------------------
# service: deadlines, cancellation, crash restore
# ---------------------------------------------------------------------------

class TestService:
    def test_deadline_lapsed_while_queued(self):
        with DecompositionService() as svc:
            fut = svc.submit(jnp.asarray(_decay(64, 32)), linalg.Rank(4),
                             deadline_s=0.0)
            svc.flush()
            with pytest.raises(linalg.DeadlineExceeded):
                fut.result(timeout=60)
            svc.drain(timeout=60)
            assert svc.metrics.export()["deadline_exceeded"] == 1

    def test_cancel_while_queued_neighbor_unaffected(self):
        arr = _decay(1024, 96, seed=3)
        with DecompositionService() as svc:
            f1 = svc.submit(linalg.HostOp(arr, block_rows=128),
                            linalg.Rank(8), seed=0)
            f2 = svc.submit(linalg.HostOp(arr, block_rows=128),
                            linalg.Rank(8), seed=1)
            f2.cancel()
            with pytest.raises((CancelledError, snap.Cancelled)):
                f2.result(timeout=120)
            assert f1.result(timeout=120).rank == 8    # neighbor unaffected
            svc.drain(timeout=120)
            assert svc.metrics.export()["cancelled"] == 1

    def test_running_cancel_is_cooperative(self, tmp_path):
        arr = _decay(4096, 256, seed=4)
        ckdir = tmp_path / "ck"
        with DecompositionService() as svc:
            fut = svc.submit(linalg.HostOp(arr, block_rows=256),
                             linalg.Rank(8), seed=2, checkpoint=str(ckdir))
            t0 = time.monotonic()
            while (not list(ckdir.glob("snap_*")) and not fut.done()
                   and time.monotonic() - t0 < 120):
                time.sleep(0.001)
            fut.cancel()
            try:
                fut.result(timeout=300)    # finished before the cancel: legal
            except CancelledError:
                pass                       # cancelled while still queued
            except snap.Cancelled as exc:  # the cooperative path under test
                assert pathlib.Path(exc.snapshot_path).is_dir()
        # whatever raced, the partial (or full) solve left durable snapshots
        assert [p for p in ckdir.glob("snap_*") if p.suffix != ".tmp"]

    def test_restore_reenqueues_interrupted_job_bit_identical(self, tmp_path):
        arr = _decay(512, 96, seed=6)
        spec, seed = linalg.Rank(8), 11
        store, ckdir = tmp_path / "store", tmp_path / "ck"
        op = linalg.as_linop(linalg.HostOp(arr, block_rows=128))
        pl = registry_mod.cached_plan(op, linalg.as_spec(spec), kind="svd",
                                      overrides=None,
                                      guard=guard.as_guard(None),
                                      validate=False)
        # crash simulation: the write-ahead record exists, the solve died
        # mid-panel with snapshots on disk, complete() never ran
        job_id = JobStore(store).record(
            op=op, spec=spec, kind="svd", seed=seed, guard_mode="off",
            validate=False, plan_fingerprint=pl.fingerprint(),
            checkpoint_dir=str(ckdir), deadline_s=None)
        assert job_id is not None
        with pytest.raises(faults.PreemptionError):
            with faults.inject("preempt", panel=5):
                linalg.decompose(linalg.HostOp(arr, block_rows=128), spec,
                                 seed=seed, checkpoint=str(ckdir))
        ref = linalg.decompose(linalg.HostOp(arr, block_rows=128), spec,
                               seed=seed)
        svc = DecompositionService.restore(str(store))
        try:
            dec = svc.restored_futures[job_id].result(timeout=300)
            assert svc.metrics.export()["resumed_jobs"] == 1
        finally:
            svc.close()
        _same(ref.factors, dec.factors)
        assert JobStore(store).pending() == []     # record retired on resolve

    def test_restore_plan_mismatch_runs_fresh(self, tmp_path):
        arr = _decay(256, 64, seed=7)
        store = tmp_path / "store"
        op = linalg.as_linop(linalg.HostOp(arr, block_rows=64))
        job_id = JobStore(store).record(
            op=op, spec=linalg.Rank(6), kind="svd", seed=2, guard_mode="off",
            validate=False, plan_fingerprint="stale|environment|changed",
            checkpoint_dir=str(tmp_path / "ck"), deadline_s=None)
        ref = linalg.decompose(linalg.HostOp(arr, block_rows=64),
                               linalg.Rank(6), seed=2)
        svc = DecompositionService.restore(str(store))
        try:
            dec = svc.restored_futures[job_id].result(timeout=300)
        finally:
            svc.close()
        _same(ref.factors, dec.factors)

    def test_jobstore_rejects_unpersistable_sources(self, tmp_path):
        class NoArray:
            shape = (8, 8)
        assert JobStore(tmp_path).record(
            op=NoArray(), spec=linalg.Rank(2), kind="svd", seed=0,
            guard_mode="off", validate=False, plan_fingerprint="x",
            checkpoint_dir=None, deadline_s=None) is None
        assert list(tmp_path.iterdir()) == []      # nothing was written

    def test_jobstore_sweeps_tmp_debris(self, tmp_path):
        (tmp_path / "job_deadbeef.tmp").mkdir(parents=True)
        store = JobStore(tmp_path)
        assert store.pending() == []
        assert not (tmp_path / "job_deadbeef.tmp").exists()

    def test_metrics_export_resilience_counters(self):
        ex = MetricsRecorder().export()
        for key in ("cancelled", "deadline_exceeded", "restarts",
                    "resumed_jobs", "checkpoint_overhead_s"):
            assert key in ex, key


# ---------------------------------------------------------------------------
# lint contract: the new state carriers are key dataclasses
# ---------------------------------------------------------------------------

def test_state_dataclasses_are_lint_keyed():
    from repro.analysis import rules
    assert "SnapshotRef" in rules.KEY_DATACLASSES
    assert "JobRecord" in rules.KEY_DATACLASSES


# ---------------------------------------------------------------------------
# kill -9 subprocess drivers (slow lane / CI resilience lane)
# ---------------------------------------------------------------------------

def _run_driver(mode, workdir):
    env = os.environ.copy()
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # a child never takes the chip
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "resume_driver.py"),
         mode, str(workdir)],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, (
        f"resume driver {mode!r} failed rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc.stdout


@pytest.mark.slow
def test_streamed_resume_survives_sigkill(tmp_path):
    assert "RESUME_STREAMED_OK" in _run_driver("streamed", tmp_path)


@pytest.mark.slow
def test_adaptive_resume_survives_sigkill(tmp_path):
    assert "RESUME_ADAPTIVE_OK" in _run_driver("adaptive", tmp_path)


@pytest.mark.slow
def test_service_restore_survives_sigkill(tmp_path):
    assert "SERVICE_RESTORE_OK" in _run_driver("service", tmp_path)


@pytest.mark.slow
def test_checkpoint_manager_crash_mid_save(tmp_path):
    assert "CKPT_CRASH_OK" in _run_driver("ckpt", tmp_path)
