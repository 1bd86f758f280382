"""repro.serve.decomp: the decomposition service.

Pins the subsystem's contracts:

  * coalesced per-request results BIT-identical to standalone
    `decompose(StackedOp(x[None]), ...)` at the request's seed — whatever
    batch the coalescer formed, and under arrival-order permutation
    (property test);
  * compiled-executable cache: at most ONE trace per distinct plan across
    N same-plan requests (asserted on `blocked.trace_count`);
  * two-lane scheduling starvation bound: a 65536 x 4096 out-of-core job
    concurrent with >= 100 small requests never makes an admitted request
    wait more than K big-job slices;
  * per-request fault isolation: a poisoned request fails alone
    (`RequestError` carrying a HealthReport), its batch neighbors keep
    bit-identical results; injected `flaky_link` transfer faults on the
    big lane never touch small-lane traffic;
  * the LRU plan cache short-circuits repeat planning (no second
    `planner.plan` call);
  * `serve.lowrank.factorize_params(service=...)` routes same-shaped
    leaves through the coalescer bit-identically to a serial service;
  * `serve.engine.Engine.generate` rejects empty prompts up-front.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import linalg
from repro.core import blocked
from repro.linalg import planner as planner_mod
from repro.serve import lowrank
from repro.serve.decomp import (
    DecompositionService,
    RequestError,
    ServiceClosed,
    ServiceOverloaded,
    trace_count,
)


def _mats(n, shape=(32, 16), seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(n)]


def _standalone(x, k, seed):
    """The service's bit-identity baseline: this request alone, batch of 1."""
    U, S, Vt = linalg.decompose(
        linalg.StackedOp(x[None]), linalg.Rank(k), seed=seed).factors
    return U[0], S[0], Vt[0]


def _identical(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# Coalescing: bit-identity and batching
# ---------------------------------------------------------------------------

def test_coalesced_bit_identical_to_standalone():
    xs = _mats(6)
    with DecompositionService(window_s=0.05, max_batch=4) as svc:
        futs = [svc.submit(x, linalg.Rank(4), seed=i) for i, x in enumerate(xs)]
        svc.flush()
        decs = [f.result(timeout=120) for f in futs]
    assert any(d.plan.batch > 1 for d in decs)  # coalescing actually happened
    for i, (x, dec) in enumerate(zip(xs, decs)):
        assert _identical(dec.factors, _standalone(x, 4, i))


def test_mixed_shapes_bucket_separately():
    a = _mats(2, (32, 16), seed=1)
    b = _mats(2, (24, 24), seed=2)
    with DecompositionService(window_s=0.05, max_batch=4) as svc:
        futs = ([svc.submit(x, linalg.Rank(4), seed=i) for i, x in enumerate(a)]
                + [svc.submit(x, linalg.Rank(4), seed=10 + i)
                   for i, x in enumerate(b)])
        svc.flush()
        decs = [f.result(timeout=120) for f in futs]
    for dec, x, seed in zip(decs, a + b, [0, 1, 10, 11]):
        assert dec.factors[0].shape[0] == x.shape[0]
        assert _identical(dec.factors, _standalone(x, 4, seed))


def test_executable_cache_one_trace_per_plan():
    """N same-shape waves -> one executable-cache plan entry per batch
    shape, each traced at most once (the subsystem's compile contract)."""
    with DecompositionService(window_s=0.05, max_batch=4) as svc:
        for wave in range(3):
            xs = _mats(4, seed=wave)
            futs = [svc.submit(x, linalg.Rank(4), seed=100 * wave + i)
                    for i, x in enumerate(xs)]
            svc.flush()
            for f in futs:
                f.result(timeout=120)
        stats = svc.executable_cache.stats()
        plans = svc.executable_cache.plans()
    assert stats["hits"] >= 1  # waves 2..3 reused wave 1's executable
    for pl in plans:
        assert trace_count(pl) <= 1, f"plan traced more than once: {pl}"


def test_plan_cache_no_replan_on_repeat(monkeypatch):
    """Satellite: the LRU plan cache must short-circuit the second plan()."""
    calls = []
    real_plan = planner_mod.plan

    def counting_plan(*a, **kw):
        calls.append(1)
        return real_plan(*a, **kw)

    linalg.clear_plan_cache()
    monkeypatch.setattr(planner_mod, "plan", counting_plan)
    x = _mats(1, seed=5)[0]
    linalg.decompose(x, linalg.Rank(4), seed=0)
    n_first = len(calls)
    assert n_first >= 1
    linalg.decompose(x, linalg.Rank(4), seed=1)  # same planning inputs
    assert len(calls) == n_first, "repeat decompose() re-planned"
    stats = linalg.plan_cache_stats()
    assert stats["hits"] >= 1


# ---------------------------------------------------------------------------
# Arrival-order permutation: per-slice seed isolation
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(perm_seed=st.integers(0, 10_000))
def test_arrival_order_permutation_irrelevant(perm_seed):
    xs = _mats(5, seed=3)
    order = np.random.default_rng(perm_seed).permutation(len(xs))
    with DecompositionService(window_s=0.05, max_batch=4) as svc:
        futs = {}
        for j in order:
            futs[int(j)] = svc.submit(xs[j], linalg.Rank(4), seed=int(j))
        svc.flush()
        decs = {j: f.result(timeout=120) for j, f in futs.items()}
    for j, x in enumerate(xs):
        assert _identical(decs[j].factors, _standalone(x, 4, j))


def test_svd_batched_seed_vector_permutation():
    """Core-level seed isolation: permuting (stack, seeds) together permutes
    the results bit-exactly — no slice reads a neighbor's randomness."""
    rng = np.random.default_rng(7)
    A = jnp.asarray(rng.standard_normal((4, 32, 16)), jnp.float32)
    seeds = jnp.asarray([11, 22, 33, 44], jnp.uint32)
    cfg = blocked.batched_cfg(planner_mod.plan(
        linalg.StackedOp(A), linalg.Rank(4)).to_config())
    U, S, Vt = blocked.svd_batched(A, 4, cfg, seed=seeds)
    perm = jnp.asarray([2, 0, 3, 1])
    Up, Sp, Vtp = blocked.svd_batched(A[perm], 4, cfg, seed=seeds[perm])
    assert _identical((Up, Sp, Vtp), (U[perm], S[perm], Vt[perm]))


# ---------------------------------------------------------------------------
# Fault isolation
# ---------------------------------------------------------------------------

def test_poisoned_request_fails_alone():
    xs = _mats(3, seed=4)
    bad = xs[1].at[3, 3].set(jnp.nan)
    batch = [xs[0], bad, xs[2]]
    with DecompositionService(window_s=0.05, max_batch=4) as svc:
        futs = [svc.submit(x, linalg.Rank(4), seed=i)
                for i, x in enumerate(batch)]
        svc.flush()
        with pytest.raises(RequestError) as exc_info:
            futs[1].result(timeout=120)
        neighbors = [futs[0].result(timeout=120), futs[2].result(timeout=120)]
    health = exc_info.value.health
    assert health is not None and not health.ok
    for (i, x) in ((0, xs[0]), (2, xs[2])):
        dec = neighbors[0 if i == 0 else 1]
        assert _identical(dec.factors, _standalone(x, 4, i))


def test_flaky_link_on_big_lane_isolated_from_small():
    """An injected transfer fault on the streamed big job must not leak
    into concurrent small-lane requests; the big request's guard ladder
    absorbs the fault (retry) so its own future still resolves."""
    rng = np.random.default_rng(8)
    # full-rank host matrix (a broadcast rank-1 view would break every QR
    # rung on its own and mask the fault-injection outcome)
    big = rng.standard_normal((4096, 256)).astype(np.float32)
    xs = _mats(6, seed=9)
    overrides = linalg.RSVDConfig(oversample=4, power_iters=0)
    with DecompositionService(window_s=0.05, max_batch=4,
                              big_threshold_s=0.0) as svc:
        with linalg.faults.inject("flaky_link", times=1):
            big_fut = svc.submit(
                linalg.HostOp(big, block_rows=512), linalg.Rank(4),
                seed=0, overrides=overrides, guard="retry")
            futs = [svc.submit(x, linalg.Rank(4), seed=i)
                    for i, x in enumerate(xs)]
            svc.flush()
            decs = [f.result(timeout=240) for f in futs]
            big_dec = big_fut.result(timeout=240)
    for i, (x, dec) in enumerate(zip(xs, decs)):
        assert _identical(dec.factors, _standalone(x, 4, i))
    assert big_dec.rank == 4
    assert big_dec.health is not None and big_dec.health.ok


# ---------------------------------------------------------------------------
# Scheduling: the starvation bound
# ---------------------------------------------------------------------------

STARVATION_K = 3  # strict-drain bound is 1; +admission/measurement races


def test_starvation_bound_under_out_of_core_job():
    """One 65536 x 4096 out-of-core solve concurrent with >= 100 small
    requests: every small request starts within STARVATION_K big-job
    slices of its submission, and everything completes."""
    rng = np.random.default_rng(10)
    # 0-stride broadcast view: 1 GiB logical, ~16 KiB resident — panels
    # materialize one block_rows slab at a time through stream_host_panels
    big = np.broadcast_to(
        rng.standard_normal((1, 4096)).astype(np.float32), (65536, 4096))
    overrides = linalg.RSVDConfig(oversample=4, power_iters=0)
    xs = _mats(4, (32, 16), seed=11)
    with DecompositionService(window_s=0.002, max_batch=4,
                              big_threshold_s=0.0, panel_group=2) as svc:
        big_fut = svc.submit(
            linalg.HostOp(big, block_rows=4096), linalg.Rank(4),
            seed=0, overrides=overrides)
        deadline = time.monotonic() + 60
        while svc.gate.big_slices == 0 and time.monotonic() < deadline:
            time.sleep(0.002)  # wait until the big job is mid-flight
        assert svc.gate.big_slices > 0, "big job never started slicing"
        futs = []
        for i in range(100):
            futs.append(svc.submit(xs[i % 4], linalg.Rank(4), seed=i))
            if i % 10 == 9:
                svc.flush()
                time.sleep(0.001)  # spread arrivals across the big job
        svc.flush()
        for f in futs:
            f.result(timeout=240)
        big_fut.result(timeout=240)
        records = svc.metrics.records()
    small = [r for r in records if r.lane == "small"]
    assert len(small) == 100
    worst = max(r.big_slices_waited for r in small)
    assert worst <= STARVATION_K, (
        f"a small request waited {worst} big-job slices (bound {STARVATION_K})")
    assert svc.gate.big_slices >= 2  # the big job really ran in slices


# ---------------------------------------------------------------------------
# Admission control, lifecycle, metrics
# ---------------------------------------------------------------------------

def test_big_lane_overload_refused():
    svc = DecompositionService(big_threshold_s=0.0, big_capacity=0)
    big = np.broadcast_to(np.ones((1, 256), np.float32), (4096, 256))
    with pytest.raises(ServiceOverloaded):
        svc.submit(linalg.HostOp(big, block_rows=512), linalg.Rank(4))
    svc.close()


def test_submit_after_close_raises():
    svc = DecompositionService()
    svc.close()
    with pytest.raises(ServiceClosed):
        svc.submit(_mats(1)[0], linalg.Rank(4))


def test_metrics_export_schema():
    xs = _mats(4, seed=12)
    with DecompositionService(window_s=0.05, max_batch=4) as svc:
        futs = [svc.submit(x, linalg.Rank(4), seed=i) for i, x in enumerate(xs)]
        svc.flush()
        for f in futs:
            f.result(timeout=120)
        m = svc.metrics.export()
    for key in ("requests", "failed", "coalescing_factor", "cache_hit_rate",
                "compiles", "compile_s_total", "queue_s_p50", "queue_s_p99",
                "latency_s_p50", "latency_s_p99", "execute_s_p50",
                "predicted_walltime_err_p50", "max_big_slices_waited"):
        assert key in m, key
    assert m["requests"] == 4
    assert m["failed"] == 0
    assert m["coalescing_factor"] >= 1.0
    assert m["latency_s_p99"] >= m["latency_s_p50"] >= 0.0


def test_concurrent_submitters_threads():
    """CI smoke shape: many threads submitting concurrently; every future
    resolves bit-identically to its standalone baseline."""
    xs = _mats(12, seed=13)
    results = {}
    lock = threading.Lock()
    with DecompositionService(window_s=0.01, max_batch=4) as svc:
        def worker(j):
            fut = svc.submit(xs[j], linalg.Rank(4), seed=j)
            with lock:
                results[j] = fut
        threads = [threading.Thread(target=worker, args=(j,))
                   for j in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        svc.flush()
        decs = {j: f.result(timeout=120) for j, f in results.items()}
    for j, x in enumerate(xs):
        assert _identical(decs[j].factors, _standalone(x, 4, j))


# ---------------------------------------------------------------------------
# Satellites: lowrank service routing, engine empty-prompt validation
# ---------------------------------------------------------------------------

def _toy_params(seed=14):
    rng = np.random.default_rng(seed)
    w = lambda s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return {
        "layer0": {"w_up": w((64, 32)), "w_down": w((32, 64))},
        "layer1": {"w_up": w((64, 32)), "w_down": w((32, 64))},
        "embed": w((128, 32)),  # not a target key: stays dense
    }


def test_factorize_params_service_matches_serial_service():
    params = _toy_params()
    with DecompositionService(window_s=0.2, max_batch=4) as svc:
        fac_c, rep_c = lowrank.factorize_params(params, rank=8, service=svc)
        coalesced = svc.metrics.export()["coalescing_factor"]
    with DecompositionService(window_s=0.2, max_batch=1) as svc1:
        fac_s, rep_s = lowrank.factorize_params(params, rank=8, service=svc1)
    assert rep_c == rep_s
    la, lb = jax.tree.leaves(fac_c), jax.tree.leaves(fac_s)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert coalesced >= 1.0
    assert isinstance(fac_c["layer0"]["w_up"], dict)  # actually factorized
    assert not isinstance(fac_c["embed"], dict)       # non-target untouched


def test_factorize_params_service_poisoned_leaf_stays_dense():
    params = _toy_params(seed=15)
    params["layer1"]["w_up"] = params["layer1"]["w_up"].at[0, 0].set(jnp.nan)
    with DecompositionService(window_s=0.2, max_batch=4) as svc:
        fac, rep = lowrank.factorize_params(params, rank=8, service=svc)
    assert np.isnan(rep["layer1/w_up"])
    assert not isinstance(fac["layer1"]["w_up"], dict)   # kept dense
    assert isinstance(fac["layer0"]["w_up"], dict)       # neighbor unharmed
    assert np.isfinite(rep["layer0/w_up"])


def test_engine_rejects_empty_prompt():
    from repro.serve.engine import EmptyPromptError, Engine, Request

    eng = Engine(None, None)  # validation fires before params/cfg are touched
    good = Request(prompt=np.array([1, 2, 3], np.int32))
    empty = Request(prompt=np.array([], np.int32))
    with pytest.raises(EmptyPromptError):
        eng.generate([good, empty])
