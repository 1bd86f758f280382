"""Compiled-executable cache: frozen ExecutionPlan -> jitted batched solve.

The service's coalescible traffic always executes through the batched body
(`core.blocked._batched_tall`, a batch of 1 for uncoalesced requests) — the
one program whose per-slice results are bit-identical whatever batch its
slices arrived in.  ExecutionPlans are frozen/hashable, so the plan itself
keys the cache; a hit returns a callable whose underlying jit trace already
exists, making the steady-state hot path re-trace-free.

Trace accounting: the `jit.traces.<key>` counter (linalg/trace.py) is
incremented INSIDE the batched body, so it ticks at trace time only.  `trace_count(plan)` maps a
plan to its body-level trace key (same orientation swap and config
normalization `svd_batched` applies) — tests and the bench assert at most
one trace per distinct plan across N same-plan requests.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Tuple

import jax

from repro.core import blocked
from repro.linalg.api import f32_matmuls
from repro.linalg.planner import ExecutionPlan


def _trace_key_for(pl: ExecutionPlan):
    """The `blocked._trace_key` this plan's batches trace under.

    Mirrors `svd_batched` exactly: wide stacks are transposed to the tall
    orientation before the jit boundary (pl.m/pl.n are already recorded
    post-orientation), and the config is normalized by `batched_cfg`."""
    cfg = blocked.batched_cfg(pl.to_config())
    return blocked._trace_key((pl.batch, pl.m, pl.n), pl.dtype, pl.k, cfg)


def trace_count(pl: ExecutionPlan) -> int:
    """How many times this plan's batched body has been traced (process-wide)."""
    return blocked.trace_count(_trace_key_for(pl))


class ExecutableCache:
    """plan -> `solve(stack, seeds) -> (U, S, Vt)`, with hit/miss stats.

    The callable routes through `blocked.svd_batched`, so orientation,
    config normalization, and the jit cache are exactly the library path's —
    a standalone `decompose(StackedOp(x[None]), ...)` call and a service
    batch compile (and share) the same program.  What this layer adds is
    plan-granular bookkeeping: hit/miss counts, first-call (compile)
    walltime per entry, and the trace-count assertion surface."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[ExecutionPlan, Callable] = {}
        self._first_call_s: Dict[ExecutionPlan, float] = {}
        self.hits = 0
        self.misses = 0

    def _build(self, pl: ExecutionPlan) -> Callable:
        cfg = pl.to_config()
        k = pl.k

        @f32_matmuls
        def solve(stack: jax.Array, seeds: jax.Array):
            return blocked.svd_batched(stack, k, cfg, seed=seeds)

        return solve

    def get(self, pl: ExecutionPlan) -> Tuple[Callable, bool]:
        """(solve callable, was_hit).  Thread-safe; builds at most once per
        plan — concurrent first requests for the same plan race only on a
        cheap closure construction, never on compilation (jax's jit cache
        deduplicates the trace underneath)."""
        with self._lock:
            fn = self._entries.get(pl)
            if fn is not None:
                self.hits += 1
                return fn, True
            self.misses += 1
            fn = self._build(pl)
            self._entries[pl] = fn
            return fn, False

    def note_first_call(self, pl: ExecutionPlan, seconds: float) -> None:
        with self._lock:
            self._first_call_s.setdefault(pl, float(seconds))

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "plans": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": self.hits / max(1, self.hits + self.misses),
                "first_call_s": dict(self._first_call_s),
                "trace_counts": {
                    repr(p): trace_count(p) for p in self._entries
                },
            }

    def plans(self) -> Tuple[ExecutionPlan, ...]:
        with self._lock:
            return tuple(self._entries)


def timed(fn: Callable, *args):
    """Run fn(*args), block on the result, return (result, walltime_s)."""
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


__all__ = ["ExecutableCache", "trace_count", "timed"]
