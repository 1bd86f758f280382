"""Concurrent-access regressions for the shared-mutable-state fixes the
RL002 lint surfaced: autotune table, fault registry, decomposition registry,
and the trace-count accounting — plus exact once-per-plan tracing when many
service threads hit the same plan simultaneously."""
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import pytest

from repro import linalg
from repro.core import blocked
from repro.kernels import autotune
from repro.linalg import faults, registry, trace
from repro.serve.decomp import cache as serve_cache

pytestmark = pytest.mark.analysis

N_THREADS = 8


def _hammer(fn, iters=200):
    """Run fn(thread_idx, iter_idx) from N_THREADS threads; re-raise the
    first worker exception (silent worker death hides races)."""
    barrier = threading.Barrier(N_THREADS)
    errors = []

    def worker(t):
        try:
            barrier.wait()
            for i in range(iters):
                fn(t, i)
        except Exception as exc:  # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(N_THREADS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]


def test_autotune_concurrent_record_lookup():
    autotune.clear()
    blocks = autotune.BlockSizes(8, 128, 128)

    def fn(t, i):
        kernel = f"k{(t + i) % 3}"
        autotune.record(kernel, (256, 256), jnp.float32, blocks, "pallas",
                        us=float(i))
        got = autotune.lookup(kernel, (256, 256), jnp.float32, "pallas")
        assert got is None or got == blocks

    _hammer(fn)
    for kernel in ("k0", "k1", "k2"):
        assert autotune.lookup(kernel, (256, 256), jnp.float32,
                               "pallas") == blocks
    autotune.clear()


def test_fault_registry_concurrent_inject():
    def fn(t, i):
        with faults.inject("nan_panel", panel=t) as fault:
            faults.fingerprint()
            assert faults._fired[id(fault)] == 0

    _hammer(fn, iters=100)
    assert not faults.any_active()
    assert not faults._fired


def test_registry_concurrent_register_and_get():
    base_kinds = set(registry.kinds())

    def execute(op, spec, pl, seed):  # pragma: no cover - never called
        raise NotImplementedError

    def fn(t, i):
        registry.register(
            registry.DecompositionKind(f"_test_kind_{t}", execute))
        assert registry.get("svd").name == "svd"
        assert registry.get(f"_test_kind_{t}").name == f"_test_kind_{t}"

    try:
        _hammer(fn, iters=100)
        for t in range(N_THREADS):
            assert registry.get(f"_test_kind_{t}") is not None
    finally:
        with registry._registry_write_lock:
            for name in set(registry.kinds()) - base_kinds:
                registry._REGISTRY.pop(name, None)


def test_plan_cache_stats_exact_under_contention():
    # The cached_plan LRU was already lock-guarded (PR 8); this pins the
    # accounting: every call lands in exactly one of hits/misses/bypasses.
    registry.clear_plan_cache()
    ops = [linalg.DenseOp(jax.ShapeDtypeStruct((64 + 8 * j, 32), jnp.float32))
           for j in range(3)]
    before = registry.plan_cache_stats()
    iters = 100

    def fn(t, i):
        pl = registry.cached_plan(ops[(t + i) % 3], 4)
        assert pl.path == "dense"

    _hammer(fn, iters=iters)
    after = registry.plan_cache_stats()
    delta = sum(after[k] - before[k] for k in ("hits", "misses", "bypasses"))
    assert delta == N_THREADS * iters


def test_trace_counter_is_exact_under_contention():
    key = ("analysis-concurrency-probe", 0)
    before = blocked.trace_count(key)
    iters = 500

    def fn(t, i):
        blocked._note_trace(key)

    _hammer(fn, iters=iters)
    # An unlocked Counter drops increments under contention; the locked one
    # must account for every single trace.
    assert blocked.trace_count(key) - before == N_THREADS * iters
    trace.reset(blocked._trace_counter(key))


def test_service_traces_once_per_plan_under_thread_storm():
    # Shape chosen to collide with no other test's trace key.
    stack = ((jnp.arange(5 * 40 * 16, dtype=jnp.float32)
              .reshape(5, 40, 16) * 0.73) % 1.0 + 0.1)
    pl = linalg.plan(linalg.StackedOp(stack), 3)
    cache = serve_cache.ExecutableCache()
    seeds = blocked.slice_seeds(0, 5)
    before = serve_cache.trace_count(pl)
    results = []

    def request(_):
        solve, _hit = cache.get(pl)
        return jax.block_until_ready(solve(stack, seeds))

    # One warm-up request compiles the plan's program (exactly one trace)...
    request(0)
    assert serve_cache.trace_count(pl) - before == 1

    with ThreadPoolExecutor(max_workers=N_THREADS) as pool:
        results = list(pool.map(request, range(N_THREADS * 2)))

    # ...and a thread storm on the warm plan must not re-trace at all —
    # the locked counter proves it exactly (an unlocked Counter could both
    # hide a stray re-trace and lose increments under contention).
    assert serve_cache.trace_count(pl) - before == 1
    u0, s0, v0 = results[0]
    for u, s, v in results[1:]:
        assert jnp.array_equal(s, s0)
