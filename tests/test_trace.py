"""Program spans and counters (linalg/trace.py).

Under `jax.profiler`, `decompose` writes its spans on the host plane,
nested as linalg/trace.py's table states; the counters count every host
panel's bytes, whatever the walk; tracing changes no factor; and the views
that predate the counters read as before.
"""
from __future__ import annotations

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import linalg
from repro.core import blocked
from repro.core.rsvd import RSVDConfig
from repro.linalg import faults, pipeline, registry, trace

M, N, K, BLOCK_ROWS = 512, 256, 10, 128
ENGINE_STAGES = ("engine.sketch", "engine.power_z", "engine.power_y",
                 "engine.orth", "engine.project", "engine.small_svd",
                 "engine.assemble")


def _matrix():
    rng = np.random.default_rng(7)
    u, _ = np.linalg.qr(rng.standard_normal((M, 64)))
    v, _ = np.linalg.qr(rng.standard_normal((N, 64)))
    return ((u * (1.0 / np.arange(1, 65) ** 2)) @ v.T).astype(np.float32)


def _host_solve(A, depth, seed=3):
    return linalg.decompose(linalg.HostOp(A, block_rows=BLOCK_ROWS),
                            linalg.Rank(K), seed=seed,
                            overrides=RSVDConfig(pipeline_depth=depth))


def _profiled_spans(tmp_path, fn):
    """(result of fn(), [(name, start_ns, end_ns, stats)]) of the program's
    host spans, read back from the trace with ProfileData."""
    from jax.profiler import ProfileData

    fn()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
        jax.block_until_ready(tuple(out.factors))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                          for ev in line.events
                          if ev.name.split(".")[0] in ("linalg", "engine",
                                                       "pipeline")]
    return out, spans


def _inside(span, spans, parents):
    """Some span named in `parents` covers `span`."""
    _, s, e, _ = span
    return any(n in parents and ps <= s and e <= pe for n, ps, pe, _ in spans)


@pytest.mark.parametrize("source", ["host_depth1", "host_depth2", "device"])
def test_decompose_spans_nest(tmp_path, source):
    A = _matrix()
    if source == "device":
        fn = lambda: linalg.decompose(jnp.asarray(A), linalg.Rank(K), seed=3)  # noqa: E731
    else:
        depth = int(source[-1])
        fn = lambda: _host_solve(A, depth)  # noqa: E731
    _, spans = _profiled_spans(tmp_path, fn)
    names = [s[0] for s in spans]
    (dec,) = [s for s in spans if s[0] == "linalg.decompose"]
    assert dec[3]["solve"] == trace.count("linalg.solves")
    for child in ("linalg.plan", "linalg.execute"):
        assert names.count(child) == 1
        assert _inside(spans[names.index(child)], spans, {"linalg.decompose"})
    if source == "device":
        assert names.count("engine.dense") == 1
        assert _inside(spans[names.index("engine.dense")], spans,
                       {"linalg.execute"})
        assert not any(n.startswith("pipeline.") for n in names)
        return
    assert set(ENGINE_STAGES) <= set(names)
    for span in spans:
        name = span[0]
        if name.startswith("engine."):
            assert _inside(span, spans, {"linalg.execute"}), name
        elif name == "pipeline.panel":
            assert _inside(span, spans, set(ENGINE_STAGES)), name
        elif name == "pipeline.put":
            assert _inside(span, spans, {"pipeline.panel"}), name
        elif name == "pipeline.slot_wait":
            assert _inside(span, spans, {"pipeline.panel", *ENGINE_STAGES}), name
    # six passes of four panels: one panel span and one put per panel
    assert names.count("pipeline.panel") == names.count("pipeline.put") == 24
    assert ("pipeline.slot_wait" in names) == (source == "host_depth2")


def _h2d(fn):
    before = trace.counters()
    fn()
    after = trace.counters()
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in ("pipeline.h2d_bytes", "pipeline.panels",
                      "pipeline.transfer_retries")}


@pytest.mark.parametrize("depth", [1, 2])
def test_h2d_bytes_count_six_passes(depth):
    A = _matrix()
    got = _h2d(lambda: _host_solve(A, depth))
    assert got["pipeline.h2d_bytes"] == 6 * A.nbytes   # 2 + 2q passes, q = 2
    assert got["pipeline.panels"] == 6 * M // BLOCK_ROWS


def test_h2d_bytes_on_flaky_link_fallback():
    """A link that stays down degrades the walk to synchronous puts, which
    are counted like the staged ones; the retries are counted too."""
    A = _matrix()
    with faults.inject("flaky_link", panel=1, times=pipeline.TRANSFER_RETRIES + 1):
        got = _h2d(lambda: _host_solve(A, 2))
    assert got["pipeline.h2d_bytes"] == 6 * A.nbytes
    assert got["pipeline.transfer_retries"] == pipeline.TRANSFER_RETRIES


def test_device_source_puts_nothing():
    A = jnp.asarray(_matrix())
    got = _h2d(lambda: linalg.decompose(linalg.DenseOp(A, block_rows=BLOCK_ROWS),
                                        linalg.Rank(K),
                                        overrides=RSVDConfig(block_rows=BLOCK_ROWS)))
    assert got == {"pipeline.h2d_bytes": 0, "pipeline.panels": 0,
                   "pipeline.transfer_retries": 0}


@pytest.mark.parametrize("source", ["host", "device"])
def test_factors_identical_with_profiler_on(tmp_path, source):
    A = _matrix()
    if source == "host":
        fn = lambda: _host_solve(A, 2)  # noqa: E731
    else:
        fn = lambda: linalg.decompose(jnp.asarray(A), linalg.Rank(K), seed=3)  # noqa: E731
    off = fn()
    on, _ = _profiled_spans(tmp_path, fn)
    for a, b in zip(off.factors, on.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_cache_stats_read_the_counters():
    registry.clear_plan_cache()
    assert registry.plan_cache_stats() == {"hits": 0, "misses": 0,
                                           "bypasses": 0, "size": 0}
    op = linalg.DenseOp(jax.ShapeDtypeStruct((96, 32), jnp.float32))
    registry.cached_plan(op, 4)
    registry.cached_plan(op, 4)
    assert registry.plan_cache_stats() == {"hits": 1, "misses": 1,
                                           "bypasses": 0, "size": 1}
    assert trace.count("plan_cache.hits") == 1
    registry.clear_plan_cache()
    assert "plan_cache.hits" not in trace.counters()


def test_trace_count_reads_the_counter():
    key = ("test-trace-probe", 1)
    assert blocked.trace_count(key) == 0
    blocked._note_trace(key)
    blocked._note_trace(key)
    assert blocked.trace_count(key) == 2
    assert trace.counters()[blocked._trace_counter(key)] == 2
    trace.reset(blocked._trace_counter(key))
    assert blocked.trace_count(key) == 0


def test_counters_exact_under_threads():
    name = "test.threads"
    workers, iters = 8, 500

    def work():
        for _ in range(iters):
            trace.add(name)

    threads = [threading.Thread(target=work) for _ in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert trace.count(name) == workers * iters
    assert trace.add(name, 5) == workers * iters + 5
    trace.reset(name)
    assert trace.count(name) == 0
