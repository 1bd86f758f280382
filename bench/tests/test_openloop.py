"""The open loop on the CPU: its schedule, how it times a request, and the
per-layer metrics that read the service's records."""
from __future__ import annotations

import importlib.util
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from test_rehearsal import BENCH

import loadgen
import openloop

OPEN = {"name": "open.t", "loop": "open", "entry": "service", "residency": "device",
        "pool": 4, "check_sample": 8, "drain_s": 5,
        "arrivals": {"process": "poisson", "rate_per_s": 1000.0}}


def test_schedule_is_one_fixed_draw_of_the_process():
    a = loadgen.schedule(OPEN, 2.0)
    assert np.array_equal(a, loadgen.schedule(OPEN, 2.0))
    assert np.array_equal(a, loadgen.schedule(OPEN, 4.0)[:len(a)])
    assert abs(len(a) / 2.0 / 1000.0 - 1) < 0.1
    assert a[0] > 0 and a[-1] < 2.0 and np.all(np.diff(a) > 0)


@pytest.mark.parametrize("arrivals", [
    {"process": "poisson", "rate_per_s": 1000.0},
    {"process": "gamma", "rate_per_s": 250.0, "cv": 3.0},
    {"process": "gamma", "rate_per_s": 4000.0, "cv": 0.5},
])
def test_gaps_have_the_stated_rate_and_spread(arrivals):
    gaps = loadgen.gaps(arrivals, np.random.default_rng(2 ** 33 + 1), 10 ** 4)
    assert abs(1.0 / gaps.mean() / arrivals["rate_per_s"] - 1.0) < 0.05
    cv = arrivals.get("cv", 1.0)   # exponential gaps: cv 1
    assert abs(gaps.std() / gaps.mean() / cv - 1.0) < 0.10


@pytest.mark.parametrize("change", [
    {"loop": "open", "entry": "decompose"},
    {"loop": "closed"},
    {"residency": "host"},
    {"arrivals": {"process": "uniform", "rate_per_s": 10.0}},
    {"arrivals": {"process": "poisson", "rate_per_s": 0}},
    {"arrivals": {"process": "poisson", "rate_per_s": True}},
    {"arrivals": {"process": "gamma", "rate_per_s": 10.0}},
    {"arrivals": {"process": "gamma", "rate_per_s": 10.0, "cv": -1.0}},
    {"drain_s": None},
    {"check_sample": 0},
])
def test_validate_refuses_a_bad_field(change):
    with pytest.raises(ValueError):
        loadgen.validate(dict(OPEN, **change))


def test_validate_takes_both_committed_kinds():
    assert loadgen.validate(dict(OPEN)) == OPEN
    closed = {"name": "c", "loop": "closed", "residency": "device", "pool": 4,
              "warmup_calls": 4, "check_sample": 32}
    assert loadgen.validate(dict(closed)) == closed


def _done(value=None):
    fut = Future()
    fut.set_result(value)
    return fut


def test_latency_counts_from_when_a_request_was_due():
    """The stub serves each request as it is submitted, but takes 0.5 s over
    the first: the sender is late for the next fifty arrivals, and their
    wait shows in the 95th percentile though each resolved the moment it
    was sent."""
    def submit(j):
        if j == 0:
            time.sleep(0.5)
        return _done(j)

    due = time.perf_counter() + 0.05 + np.arange(60) * 0.01
    arrivals = openloop.send(submit, due, keep={0, 59})
    assert arrivals.wait(time.perf_counter() + 5)
    since_due = arrivals.latencies_s(time.perf_counter())
    since_sent = arrivals.done - arrivals.sent
    assert np.percentile(since_due, 95) > 0.3
    assert np.percentile(since_sent, 95) < 0.05
    assert sorted(arrivals.kept) == [0, 59] and arrivals.kept[59] == 59
    assert arrivals.n_failed() == 0


def test_a_slow_request_shows_in_the_tail():
    """A stub service that serves one request at a time from a queue, and
    sleeps 0.3 s on one of them: the requests queued behind it wait."""
    done = threading.Event()
    pending = []
    lock = threading.Condition()

    def worker():
        while not done.is_set() or pending:
            with lock:
                while not pending and not done.is_set():
                    lock.wait(0.05)
                if not pending:
                    continue
                j, fut = pending.pop(0)
            if j == 5:
                time.sleep(0.3)
            fut.set_result(j)

    def submit(j):
        fut = Future()
        with lock:
            pending.append((j, fut))
            lock.notify()
        return fut

    thread = threading.Thread(target=worker)
    thread.start()
    try:
        due = time.perf_counter() + 0.02 + np.arange(40) * 0.005
        arrivals = openloop.send(submit, due, keep=set())
        assert arrivals.wait(time.perf_counter() + 5)
    finally:
        done.set()
        thread.join(timeout=5)
    assert not thread.is_alive()
    assert np.percentile(arrivals.latencies_s(time.perf_counter()), 95) > 0.15


def test_refused_and_unresolved_arrivals_fail():
    def submit(j):
        if j == 1:
            raise RuntimeError("refused")
        return Future() if j == 2 else _done(j)

    due = time.perf_counter() + np.arange(4) * 0.001
    arrivals = openloop.send(submit, due, keep=set())
    assert not arrivals.wait(time.perf_counter() + 0.2)
    assert arrivals.n_failed() == 2
    assert np.isnan(arrivals.done[2]) and arrivals.failed[1]


def test_warm_up_sizes_follow_the_service_default():
    class Service:
        def __init__(self, *, max_batch: int = 8):
            pass

    class Odd:
        def __init__(self, *, max_batch: int = 6):
            pass

    assert openloop.batch_sizes(Service) == [1, 2, 4, 8]
    assert openloop.batch_sizes(Odd) == [1, 2, 4, 6]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(coalesced, queue_s, execute_s, failed=False):
    return SimpleNamespace(coalesced=coalesced, queue_s=queue_s, execute_s=execute_s,
                           failed=failed)


def test_service_metrics_count_each_batch_once():
    # a batch of 4 (execute 8 ms), a batch of 2 (6 ms), one alone (3 ms),
    # and a failed request that counts nowhere
    records = ([_record(4, 0.001 * i, 0.008) for i in range(4)]
               + [_record(2, 0.010, 0.006)] * 2 + [_record(1, 0.020, 0.003)]
               + [_record(1, 5.0, 0.0, failed=True)])
    ctx = {"requests": records}
    assert _reader("coalescing_factor")(ctx) == pytest.approx(7 / 3)
    assert _reader("execute_ms_per_solve")(ctx) == pytest.approx((8 + 6 + 3) / 7)
    assert _reader("queue_ms_p50")(ctx) == pytest.approx(3.0)
    for name in ("coalescing_factor", "execute_ms_per_solve", "queue_ms_p50"):
        assert _reader(name)({"requests": []}) is None
