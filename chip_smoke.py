#!/usr/bin/env python3
"""Run the decomposition system's main paths once on a TPU and check them.

    python chip_smoke.py [--seed N]             one chip: dense, streamed,
                                                sparse and service phases
    python chip_smoke.py --chips 4 [--seed N]   four chips: a row-sharded
                                                solve, and one-chip solves
                                                of the same matrix

Every phase factors a matrix with a known spectrum (sigma_i = 1/i^2, the
"fast" decay of core/spectra.py), built on the device from --seed, through
`linalg.decompose` or `DecompositionService`, and prints one JSON line: the
plan, which of the phase's compiled programs hold a Pallas kernel
(`tpu_custom_call`), the residual against the optimal truncation error, the
largest relative error of the leading singular values, and the walltime
(ended by `block_until_ready`) with the time spent lowering and compiling
counted apart, and the time it took to build the phase's matrix.

A failed check raises, so the script exits non-zero and prints no result.
Without a TPU, or on a chip that roofline/hw.py does not describe, it stops
before the first phase.  The last line of a passing run is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Everything runs in this one process: no phase starts a child process.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import sys
import tempfile
import threading
import time
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: past this many seconds the run dumps every thread's stack and exits 1,
#: inside the 1200 s a chip run is given
WATCHDOG_S = 1100
#: a residual above this multiple of the optimal truncation error fails
RESIDUAL_FACTOR = 1.1
#: how many leading singular values are compared with the known spectrum
LEADING = 10
#: four chips against one chip running the sharded plan's own settings, on
#: the same matrix: the largest relative difference of the leading singular
#: values, and of the two residuals.  Both solves run the same numerics and
#: differ only in summation order (each lands near 1e-5 of the known
#: spectrum); a sharding fault — a shard on the wrong rows, a lost psum —
#: moves either number by O(1).
SHARDED_SIGMA_RTOL = 1e-3
SHARDED_RESIDUAL_RTOL = 1e-2

#: lowering and XLA compilation, once per compiled program (tracing is left
#: out: a nested jit's trace time lies inside its caller's and would count
#: twice)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds jax spends lowering and compiling, from its own monitoring
    events (service threads compile too, hence the lock)."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration


class Phase:
    """One phase's clock and compiled programs.

    jax writes every program it compiles during the phase (persistent-cache
    hits included) to a fresh directory; `kernel_programs` names those that
    hold a `tpu_custom_call`."""

    def __init__(self, name: str, root: Path, clock: CompileClock):
        self.name, self._dir, self._clock = name, root / name, clock
        self.record = {"phase": name}
        self._built = time.perf_counter()

    @contextmanager
    def timed(self):
        import jax

        jax.config.update("jax_dump_ir_to", str(self._dir))
        c0, t0 = self._clock.seconds, time.perf_counter()
        self.record["setup_s"] = t0 - self._built
        try:
            yield
        finally:
            self.record["wall_s"] = time.perf_counter() - t0
            self.record["compile_s"] = self._clock.seconds - c0
            jax.config.update("jax_dump_ir_to", "")

    def kernel_programs(self):
        files = sorted(self._dir.glob("*.mlir"))
        hits = [p.name for p in files if "tpu_custom_call" in p.read_text()]
        self.record["programs"] = len(files)
        self.record["kernel_programs"] = hits
        self.record["tpu_custom_call"] = bool(hits)
        return hits

    def require_kernel(self, program: str) -> None:
        """Fail unless a compiled program whose name holds `program` has a
        Pallas kernel in it."""
        hits = self.kernel_programs()
        check(any(program in h for h in hits),
              f"{self.name}: no compiled {program!r} program holds "
              f"tpu_custom_call (kernel programs: {hits})")

    def score(self, residual, optimal, sigma, sigma_true) -> None:
        self.record.update(judge(self.name, residual, optimal, sigma, sigma_true))

    def emit(self) -> None:
        print(json.dumps(self.record), flush=True)


def judge(name: str, residual, optimal, sigma, sigma_true) -> dict:
    """Hold one solve to the oracle: its residual within RESIDUAL_FACTOR of
    the optimal truncation error; also the largest relative error of its
    LEADING singular values."""
    import numpy as np

    r, o = float(residual), float(optimal)
    check(np.isfinite(r) and r <= RESIDUAL_FACTOR * o,
          f"{name}: residual {r:.6e} above {RESIDUAL_FACTOR} x optimal {o:.6e}")
    got = np.asarray(sigma, np.float64)[:LEADING]
    want = np.asarray(sigma_true, np.float64)[:len(got)]
    return dict(residual=r, optimal=o, residual_ratio=r / o,
                sigma_rel_err_max=float(np.max(np.abs(got - want) / want)))


def _ready(x):
    import jax

    return jax.block_until_ready(x)


def dense_phase(phase: Phase, seed: int, m=65536, n=4096, k=118):
    """`linalg.decompose(A, Rank(k))` on a device array: the fused path."""
    from repro import linalg
    from repro.core.spectra import make_test_matrix

    A, sig = _ready(make_test_matrix(m, n, "fast", seed=seed))
    pl = linalg.plan(A, linalg.Rank(k))
    phase.record["plan"] = pl.describe()
    check(pl.fused_power and pl.fused_sketch and pl.kernel_backend == "pallas",
          f"dense plan is not the fused Pallas path: {pl.describe()}")
    with phase.timed():
        dec = linalg.decompose(A, linalg.Rank(k), seed=seed)
        _ready(dec.factors)
    t0 = time.perf_counter()
    _ready(linalg.decompose(A, linalg.Rank(k), seed=seed).factors)
    phase.record["warm_s"] = time.perf_counter() - t0
    phase.require_kernel("randomized_svd_dense")
    phase.score(linalg.residual(A, dec.factors, block_rows=16384),
                linalg.truncation_error(sig, k), dec.factors[1], sig)
    return A, sig


def streamed_phase(phase: Phase, seed: int, A, sig, k=118):
    """The same matrix from host memory: `HostOp`, prefetch depth 2."""
    import numpy as np

    from repro import linalg

    op = linalg.HostOp(np.asarray(A), pipeline_depth=2)
    pl = linalg.plan(op, linalg.Rank(k))
    phase.record["plan"] = pl.describe()
    check(pl.path == "streamed" and pl.pipeline_depth == 2,
          f"host source did not plan a depth-2 stream: {pl.describe()}")
    with phase.timed():
        dec = linalg.decompose(op, linalg.Rank(k), seed=seed)
        _ready(dec.factors)
    phase.require_kernel("sketch_gram")
    phase.score(linalg.residual(A, dec.factors, block_rows=16384),
                linalg.truncation_error(sig, k), dec.factors[1], sig)


def sparse_phase(phase: Phase, seed: int, m=65536, n=4096, m0=4096, n0=256,
                 k=118, tile=128):
    """A `SparseOp` whose nonzeros sit in whole tiles: the m0 x n0 block D of
    known spectrum, its 128-row chunks scattered over as many row blocks and
    its 128-column chunks over as many column blocks — a row and column
    permutation of [D 0; 0 0], so A's spectrum is D's, and the block-ELL
    pack holds n0 / 128 tiles per row block (fill n0 / n)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import sparse as jsparse

    from repro import linalg
    from repro.core.spectra import make_test_matrix

    D, sig = make_test_matrix(m0, n0, "fast", seed=seed + 2)
    rng = np.random.default_rng(seed)
    row_blocks = np.sort(rng.choice(m // tile, m0 // tile, replace=False))
    col_blocks = np.sort(rng.choice(n // tile, n0 // tile, replace=False))
    rows = (row_blocks[:, None] * tile + np.arange(tile)).ravel()
    cols = (col_blocks[:, None] * tile + np.arange(tile)).ravel()
    A = jnp.zeros((m, n), jnp.float32).at[rows[:, None], cols[None, :]].set(D)
    op = linalg.SparseOp(jsparse.BCOO.fromdense(A, nse=m0 * n0))
    pl = linalg.plan(op, linalg.Rank(k))
    phase.record["plan"] = pl.describe()
    check(pl.path == "sparse" and pl.fused_sketch,
          f"sparse source did not plan the fused SpMM sketch: {pl.describe()}")
    with phase.timed():
        dec = linalg.decompose(op, linalg.Rank(k), seed=seed)
        _ready(dec.factors)
    phase.require_kernel("spmm_sketch")
    phase.score(linalg.residual(A, dec.factors, block_rows=16384),
                linalg.truncation_error(sig, k), dec.factors[1], sig)


def service_phase(phase: Phase, seed: int, small=(2048, 512), n_small=4,
                  big=(16384, 1024), k=8):
    """`DecompositionService`: n_small same-shape Rank(k) requests, PCA-sized
    feature batches that coalesce into one batch, and one host-resident job
    that the planner streams on the big lane."""
    import numpy as np

    from repro import linalg
    from repro.core.spectra import make_test_matrix
    from repro.serve.decomp import DecompositionService

    cases = [make_test_matrix(*small, "fast", seed=seed + 10 + i)
             for i in range(n_small)]
    A_big, sig_big = make_test_matrix(*big, "fast", seed=seed + 20)
    big_op = linalg.HostOp(np.asarray(A_big))
    with phase.timed():
        with DecompositionService(window_s=30.0, max_batch=n_small) as svc:
            futs = [svc.submit(A, linalg.Rank(k), seed=seed + i)
                    for i, (A, _) in enumerate(cases)]
            big_fut = svc.submit(big_op, linalg.Rank(k), seed=seed)
            decs = [f.result(timeout=900) for f in futs]
            big_dec = big_fut.result(timeout=900)
            _ready([d.factors for d in decs + [big_dec]])
        records = svc.metrics.records()
    phase.record["plan"] = decs[0].plan.describe()
    phase.record["big_plan"] = big_dec.plan.describe()
    coalesced = sorted(r.coalesced for r in records if r.lane == "small")
    phase.record["coalesced"] = coalesced
    check(coalesced == [n_small] * n_small,
          f"service: small requests did not share one batch ({coalesced})")
    check(big_dec.plan.path == "streamed"
          and any(r.lane == "big" for r in records),
          "service: the host-resident job did not run streamed on the big lane")
    phase.require_kernel("batched_tall")
    scores = [judge("service", linalg.residual(A, dec.factors),
                    linalg.truncation_error(sig, k), dec.factors[1], sig)
              for (A, sig), dec in zip(cases + [(A_big, sig_big)], decs + [big_dec])]
    ratios = [sc["residual_ratio"] for sc in scores]
    phase.record.update(
        residual_ratio=max(ratios), residual_ratios=ratios,
        sigma_rel_err_max=max(sc["sigma_rel_err_max"] for sc in scores))


def sharded_builder(m: int, n: int, seed: int, mesh, axis: str = "data"):
    """jit of (sigma, V) -> A = U diag(sigma) V^T, row-sharded on `mesh`.

    U = G R^-1 is CholeskyQR of the Gaussian G that `sketch_matrix(m, n,
    seed)` would hold: each device makes its own rows of G, only the n x n
    Gram is reduced, and A's rows come from one product G (R^-1 diag(sigma)
    V^T), so a device holds its rows of G and of A and nothing larger.  G
    is tall (m >> n), so kappa(G) ~ 1.3 and one CholeskyQR pass leaves U
    orthonormal to f32 rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.core.sketch import normal_from_index

    m_loc = m // mesh.shape[axis]

    def local(sig, V):
        row0 = jax.lax.axis_index(axis).astype(jnp.uint32) * np.uint32(m_loc)
        rows = row0 + jnp.arange(m_loc, dtype=jnp.uint32)
        G = normal_from_index(rows[:, None] * np.uint32(n)
                              + jnp.arange(n, dtype=jnp.uint32)[None, :], seed)
        R = jnp.linalg.cholesky(jax.lax.psum(G.T @ G, axis)).T
        R_inv = jax.lax.linalg.triangular_solve(
            R, jnp.eye(n, dtype=G.dtype), left_side=False, lower=False)
        return G @ ((R_inv * sig[None, :]) @ V.T)

    return jax.jit(shard_map(local, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(axis, None)))


def sharded_test_matrix(m: int, n: int, seed: int, mesh):
    """(A, sigma): the known-spectrum matrix of `sharded_builder`."""
    import jax

    from repro.core.spectra import random_orthogonal, spectrum

    sig = spectrum(n, "fast")
    with jax.default_matmul_precision("highest"):
        V = random_orthogonal(n, n, seed + 1)
        return _ready(sharded_builder(m, n, seed, mesh)(sig, V)), sig


def sharded_phases(root: Path, clock: CompileClock, seed: int, devices,
                   m=262144, n=4096, k=118):
    """`ShardedOp(A, mesh, "data")` over four chips, then two one-chip
    solves of the same matrix: the default plan (the fused fast preset),
    held to the oracle, and one with the sharded plan's own settings, held
    to the oracle and to the four-chip result within SHARDED_*_RTOL."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro import linalg

    four = Phase("sharded", root, clock)
    mesh = Mesh(np.array(devices), ("data",))
    A, sig = sharded_test_matrix(m, n, seed, mesh)
    opt = linalg.truncation_error(sig, k)
    op = linalg.ShardedOp(A, mesh, "data")
    pl4 = linalg.plan(op, linalg.Rank(k))
    four.record["plan"] = pl4.describe()
    four.record["chips"] = len(devices)
    with four.timed():
        dec4 = linalg.decompose(op, linalg.Rank(k), seed=seed)
        _ready(dec4.factors)
    four.kernel_programs()
    check(four.record["tpu_custom_call"],
          "sharded: no compiled program holds tpu_custom_call")
    four.score(linalg.residual(op, dec4.factors), opt, dec4.factors[1], sig)
    four.emit()

    A1 = _ready(jax.device_put(A, devices[0]))
    del op, A
    for name, overrides in (("one_chip", None),
                            ("one_chip_sharded_plan", pl4.to_config())):
        one = Phase(name, root, clock)
        pl1 = linalg.plan(A1, linalg.Rank(k), overrides=overrides)
        one.record["plan"] = pl1.describe()
        with one.timed():
            dec1 = linalg.decompose(A1, linalg.Rank(k), seed=seed,
                                    overrides=overrides)
            _ready(dec1.factors)
        one.require_kernel("randomized_svd_dense")
        one.score(linalg.residual(A1, dec1.factors, block_rows=16384), opt,
                  dec1.factors[1], sig)
        s4 = np.asarray(dec4.factors[1], np.float64)[:LEADING]
        s1 = np.asarray(dec1.factors[1], np.float64)[:LEADING]
        sigma_diff = float(np.max(np.abs(s4 - s1) / s1))
        res_diff = abs(four.record["residual"] - one.record["residual"]) \
            / one.record["residual"]
        one.record.update(vs_sharded_sigma_rel_diff=sigma_diff,
                          vs_sharded_residual_rel_diff=res_diff)
        if overrides is not None:
            check(not (pl1.fused_power or pl1.fused_sketch),
                  f"one-chip solve did not take the sharded plan's unfused "
                  f"settings: {pl1.describe()}")
            check(sigma_diff <= SHARDED_SIGMA_RTOL,
                  f"four-chip and one-chip leading singular values differ "
                  f"by {sigma_diff:.3e} > {SHARDED_SIGMA_RTOL}")
            check(res_diff <= SHARDED_RESIDUAL_RTOL,
                  f"four-chip and one-chip residuals differ by "
                  f"{res_diff:.3e} > {SHARDED_RESIDUAL_RTOL}")
        one.emit()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded four-chip solve and "
                         "the one-chip solve it is compared with")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.roofline import hw

    hw.require_described(dev.device_kind)
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}")
    print(json.dumps({"compile_cache": cache_dir, "device_kind": dev.device_kind,
                      "devices": len(devices)}), flush=True)

    clock = CompileClock()
    with tempfile.TemporaryDirectory() as tmp:
        root = Path(tmp)
        if args.chips == 4:
            sharded_phases(root, clock, args.seed, devices[:4])
        else:
            dense = Phase("dense", root, clock)
            A, sig = dense_phase(dense, args.seed)
            dense.emit()
            streamed = Phase("streamed", root, clock)
            streamed_phase(streamed, args.seed, A, sig)
            streamed.emit()
            del A
            sparse = Phase("sparse", root, clock)
            sparse_phase(sparse, args.seed)
            sparse.emit()
            service = Phase("service", root, clock)
            service_phase(service, args.seed)
            service.emit()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
