"""Program spans and counters of the solve path.

Spans are `jax.profiler.TraceAnnotation`s: under `jax.profiler` each lands
on the profiler's host plane, on the same clock as the device trace, so a
reader can say what the host was doing while the chip sat idle.  With no
profiler running a span costs about a microsecond.  Spans sit only in host
code that runs once per call, pass or panel, never inside a traced
function (device-side stages are `jax.named_scope`s in core/rsvd.py, which
reach the device trace as each op's `tf_op` stat).

    span                where                               children
    linalg.decompose    api.decompose, the whole call        linalg.plan,
                        (``solve=`` the `linalg.solves` count) linalg.execute
    linalg.plan         registry.cached_plan
    linalg.execute      one execution of the registry entry  engine.*
    engine.dense        host dispatch of the jitted dense solve
    engine.sketch, engine.power_z, engine.power_y, engine.orth,
    engine.project, engine.small_svd, engine.assemble
                        the stages of the streamed engine   pipeline.panel
                        (core/blocked.py), one per pass or step
    pipeline.panel      production of one panel (slice,     pipeline.put,
                        put, probes), whatever the walk     pipeline.slot_wait
    pipeline.put        host-to-device move of one host panel
    pipeline.slot_wait  wait for a staging slot's earlier transfer

Counters are process-wide and thread-safe (service workers solve
concurrently); `counters()` is a snapshot:

    linalg.solves                  calls of `decompose`
    plan_cache.hits / .misses / .bypasses
                                   the LRU plan cache (registry.plan_cache_stats)
    jit.traces.<key>               traces of the batched body and of the
                                   streamed engine's orth programs
                                   (blocked.trace_count)
    engine.orth.calls              compiled orth steps the streamed engine ran
    pipeline.panels                host panels put on the device
    pipeline.h2d_bytes             bytes those puts moved, staging padding included
    pipeline.transfer_retries      puts retried after a transfer error
"""
from __future__ import annotations

import collections
import threading
from typing import Dict

import jax

#: ``span(name, **ids)``: a host span on the profiler's clock
span = jax.profiler.TraceAnnotation

_counts: collections.Counter = collections.Counter()
_counts_lock = threading.Lock()


def add(name: str, n: int = 1) -> int:
    """Add `n` to the counter `name`; returns its new value."""
    with _counts_lock:
        _counts[name] += n
        return _counts[name]


def count(name: str) -> int:
    with _counts_lock:
        return _counts.get(name, 0)


def counters() -> Dict[str, int]:
    """A snapshot of every counter."""
    with _counts_lock:
        return dict(_counts)


def reset(prefix: str) -> None:
    """Drop every counter whose name starts with `prefix`."""
    with _counts_lock:
        for name in [k for k in _counts if k.startswith(prefix)]:
            del _counts[name]
