"""The open loop: independent clients whose calls reach
`DecompositionService.submit` on a fixed schedule (loadgen.schedule).

Set-up builds the service with its defaults, the settings its users get,
and warms up every batch size its coalescer pads to (1, 2, 4, ... up to
`max_batch`): each a group of calls sealed with `flush()`, so that the
window compiles nothing.

The window.  One sender, the calling thread, sleeps until each arrival is
due and submits it; it does no array work, the pool being built in set-up.
Each future's done callback stamps the host clock as it resolves, and the
service resolves a future only once its factors are ready.  A request's
latency runs from when it was due, not from when the sender got to it, so
a late sender cannot hide a slow service.  After the last arrival the loop
waits at most `drain_s` for the rest; an arrival that has not resolved by
then, or that was refused or resolved with an error, counts as failed.

The check's sample (arrivals drawn from the run seed) and two arrivals of
one pool member are the only results kept; every other result is dropped
as it resolves.
"""
from __future__ import annotations

import functools
import inspect
import random
import sys
import threading
import time
import traceback

import jax
import numpy as np

import loadgen
import sources
import trace_reduce

#: how often set-up submits a batch size whose group the admission window
#: split, before it goes on
WARMUP_TRIES = 3
#: seconds `close` waits for the service after the drain
CLOSE_TIMEOUT_S = 5.0


class Arrivals:
    """What became of each arrival of one window: when it was due, sent
    and resolved (host clock), whether it failed, and the kept results."""

    def __init__(self, due: np.ndarray, keep: set):
        n = len(due)
        self.due = due
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.kept: dict = {}
        self._keep = keep
        self._cond = threading.Condition()
        self._resolved = 0

    def resolve(self, j: int, fut) -> None:
        """Done callback of arrival j's future (any thread)."""
        t = time.perf_counter()
        failed = fut.cancelled() or fut.exception() is not None
        with self._cond:
            self.done[j] = t
            self.failed[j] = failed
            if not failed and j in self._keep:
                self.kept[j] = fut.result()
            self._resolved += 1
            self._cond.notify_all()

    def refuse(self, j: int) -> None:
        """Arrival j's submit raised."""
        self.resolve(j, _failed_future())

    def wait(self, until: float) -> bool:
        """Block until every arrival has resolved or the host clock passes
        `until`; whether all resolved."""
        with self._cond:
            while self._resolved < len(self.due):
                left = until - time.perf_counter()
                if left <= 0:
                    return False
                self._cond.wait(left)
        return True

    def latencies_s(self, until: float) -> np.ndarray:
        """Due to resolved, per arrival; one still unresolved counts until
        `until`."""
        with self._cond:
            return np.where(np.isnan(self.done), until, self.done) - self.due

    def n_failed(self) -> int:
        with self._cond:
            return int(self.failed.sum() + np.isnan(self.done).sum())


def _failed_future():
    from concurrent.futures import Future

    fut = Future()
    fut.set_exception(RuntimeError("submit raised"))
    return fut


def send(submit, due: np.ndarray, keep: set) -> Arrivals:
    """Call `submit(j)`, which returns a future, for each arrival j at host
    time `due[j]`, or at once where the sender is late."""
    arrivals = Arrivals(due, keep)
    for j, t in enumerate(due):
        delay = t - time.perf_counter()
        if delay > 0:
            with jax.profiler.TraceAnnotation("sleep"):
                time.sleep(delay)
        arrivals.sent[j] = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("submit"):
                fut = submit(j)
        except Exception:  # a refused call counts in `failed`
            if not arrivals.failed.any():
                traceback.print_exc()
            arrivals.refuse(j)
            continue
        fut.add_done_callback(functools.partial(arrivals.resolve, j))
    return arrivals


def batch_sizes(service_cls) -> list[int]:
    """Every size the service's coalescer pads a batch to, at its default
    `max_batch`."""
    max_batch = inspect.signature(service_cls).parameters["max_batch"].default
    sizes = [1]
    while sizes[-1] < max_batch:
        sizes.append(min(2 * sizes[-1], max_batch))
    return sizes


class OpenLoop:
    """Set-up, one or more windows, and the check's sample, of one open-loop
    cell on a pool already on the device: each call submits its member as
    `sources.program_inputs` gives it, under the configuration's spec."""

    def __init__(self, pool, cfg: dict, traffic: dict, run_seed: int):
        from repro import linalg
        from repro.serve import decomp

        self.traffic, self.seed = traffic, run_seed
        self.srcs = sources.program_inputs(linalg, pool)
        self.spec = sources.program_spec(linalg, cfg)
        service_cls = decomp.DecompositionService
        self.svc = service_cls()
        self.calls = 0
        for size in batch_sizes(service_cls):
            self._warm_up(size)

    def _submit(self, i: int):
        member, seed = loadgen.call(self.traffic, self.seed, i)
        return self.svc.submit(self.srcs[member], self.spec, seed=seed)

    def _warm_up(self, size: int) -> None:
        """The next `size` calls as one batch; again, with the same calls
        and up to WARMUP_TRIES times, where the admission window split it."""
        for _ in range(WARMUP_TRIES):
            futures = [self._submit(self.calls + i) for i in range(size)]
            self.svc.flush()
            for fut in futures:
                fut.result()
            if all(r.coalesced == size for r in self.svc.metrics.records()[-size:]):
                break
        self.calls += size

    def window(self, seconds: float) -> tuple[float, float]:
        """Send the schedule of `seconds`, then drain; (start, end) on the
        host clock, the end being the last resolution or the drain's end."""
        offsets = loadgen.schedule(self.traffic, seconds)
        n = len(offsets)
        rng = random.Random(self.seed)
        self.sampled = sorted(rng.sample(range(n), min(self.traffic["check_sample"], n)))
        # two arrivals of one pool member under different sketch seeds
        pool = self.traffic["pool"]
        self.pair = (0, pool) if n > pool else None
        keep = set(self.sampled) | set(self.pair or ())
        self.records_from = len(self.svc.metrics.records())
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            t0 = time.perf_counter()
            self.t0, self.t_close = t0, t0 + seconds
            self.arrivals = send(lambda j: self._submit(self.calls + j), t0 + offsets, keep)
            drained = self.arrivals.wait(time.perf_counter() + self.traffic["drain_s"])
            t1 = (float(np.max(self.arrivals.done, initial=t0)) if drained
                  else time.perf_counter())
        self.t1 = t1
        return t0, t1

    # ---- what the harness reads after the window --------------------------

    @property
    def attempted(self) -> int:
        return len(self.arrivals.due)

    @property
    def failed(self) -> int:
        return self.arrivals.n_failed()

    @property
    def solves(self) -> list:
        a = self.arrivals
        return [{"t_due": a.due[j], "t_sent": a.sent[j], "t_ready": a.done[j]}
                for j in range(len(a.due)) if not a.failed[j] and not np.isnan(a.done[j])]

    def latencies_ms(self):
        return 1e3 * self.arrivals.latencies_s(self.t1)

    def end_to_end(self, setup_s: float, window_s: float) -> dict:
        """The 95th percentile of latency over every arrival, and, for a
        cell offered more than the service sustains, the requests that
        resolved by the window's close per second of the window."""
        a = self.arrivals
        done = a.done[~a.failed] <= self.t_close
        return {
            "latency_ms_p95": (float(np.percentile(self.latencies_ms(), 95))
                               if self.attempted else None),
            "solves_per_s": float(done.sum()) / (self.t_close - self.t0),
            "setup_s": setup_s,
        }

    def ctx(self) -> dict:
        """The service's own records of the window's requests, and every
        arrival's latency."""
        return {"requests": self.svc.metrics.records()[self.records_from:],
                "latencies_ms": self.latencies_ms()}

    @property
    def sample(self) -> list:
        kept = self.arrivals.kept
        return [(self._member(j), kept[j].factors) for j in self.sampled if j in kept]

    @property
    def repeat(self):
        kept = self.arrivals.kept
        if self.pair is None or not all(j in kept for j in self.pair):
            return None
        return tuple(kept[j].factors for j in self.pair)

    def _member(self, j: int) -> int:
        return loadgen.call(self.traffic, self.seed, self.calls + j)[0]

    def close(self) -> None:
        """Print how late the sender ran and the service's own summary to
        stderr, and close the service."""
        late_ms = 1e3 * (self.arrivals.sent - self.arrivals.due)
        print(f"open loop: {self.attempted} arrivals, {self.failed} failed; sender "
              f"late (ms) p50 {float(np.nanpercentile(late_ms, 50))!r}, p95 "
              f"{float(np.nanpercentile(late_ms, 95))!r}, max "
              f"{float(np.nanmax(late_ms))!r}", file=sys.stderr)
        export = getattr(self.svc.metrics, "export", None)
        if export is not None:
            print(f"service metrics (set-up included): {export()}", file=sys.stderr)
        self.svc.close(timeout=CLOSE_TIMEOUT_S)
