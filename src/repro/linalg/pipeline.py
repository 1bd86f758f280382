"""Overlapped out-of-core panel pipeline: double-buffered prefetch.

The streamed/adaptive paths consume A one row panel at a time.  Before this
module, every panel was moved host->device *synchronously* — the sketch /
power GEMMs sat idle during the transfer and the transfer engine sat idle
during the GEMMs, so out-of-core walltime was ``sum(transfer) + sum(compute)``.
Lu et al. (arXiv:1706.07191) show the out-of-core block rSVD bottleneck is
exactly this serialization: with the copy of panel *i+1* issued while panel
*i* computes, walltime drops to ``max(transfer, compute)`` per panel plus a
fill/drain term (the overlap model in roofline/rsvd_model.py).

Two primitives, composed by `operators.prefetch_panels`:

  stream_host_panels   host (numpy) slices staged through a ring of `depth`
                       reusable uniform staging buffers (CUDA pinned-buffer
                       discipline, jax edition).  The tail panel is ZERO-
                       PADDED so every transfer has the same (block, n)
                       shape — one transfer program, jit-stable consumers —
                       and sliced back to its true height on device, so
                       yielded values are bit-identical to the synchronous
                       `jnp.asarray(array[lo:hi])`.
  lookahead            generic depth-deep pull-ahead over any panel
                       iterator: jax dispatches asynchronously, so *pulling*
                       panel i+1 (its slice / transfer / per-panel compose)
                       enqueues its production while the consumer's compute
                       on panel i is still running.

Only transfer ORDER changes — never arithmetic: each yielded panel holds
exactly the bytes the synchronous path would have moved, so every consumer
(core/blocked.py, core/adaptive.py, linalg.residual, HostOp products) stays
bit-identical at fixed seed, prefetched or not (tests/test_pipeline.py).

Depth resolution: an explicit ``depth`` argument wins; else the ambient
`default_depth(...)` scope (how the execution planner's ``pipeline_depth``
reaches duck-typed consumers like core/adaptive.py without threading a
parameter through every layer); else DEFAULT_DEPTH for host-resident
sources and 1 (no prefetch — today's behavior) for device-resident ones.

Early stop is safe: a consumer that abandons the iterator (adaptive QB
meeting its tolerance mid-stream) or raises mid-stream closes the
generator, whose ``finally`` fences every in-flight transfer before the
staging ring is released (`_await_in_flight`) — no DMA is ever left
reading a buffer a later stream may rewrite, and no estimator state ever
saw the un-consumed panels.

Fault tolerance (PR 7): the host->device put of each staged panel runs
under bounded retry-with-backoff (`TRANSFER_RETRIES`); a link that stays
down degrades the REST of the walk to the synchronous per-panel path
(`jnp.asarray`) instead of failing the solve — values stay bit-identical,
only overlap is lost.  Each produced panel also passes `_panel_probe`:
the fault-injection hooks (linalg/faults.py), the guard's per-panel
finiteness probe, and the `validate=` screen (raising a ValueError that
names the offending panel) all live there, and all cost nothing when
inactive.

Tracing (linalg/trace.py): every host panel reaches the device through
`put_panel`, under a `pipeline.put` span, counted in `pipeline.panels` and
`pipeline.h2d_bytes`.  The production of each host panel (slice, staging,
put, probes) runs under a `pipeline.panel` span, on the ring, the
synchronous walk and its fallback alike, and a wait for a staging slot's
earlier transfer under `pipeline.slot_wait`.  Device-resident panels are
lazy slices: nothing is staged, and `lookahead` adds no span.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.linalg import faults as faults_mod
from repro.linalg import guard as guard_mod
from repro.linalg import trace

#: bounded retry of a failed staging transfer, with exponential backoff
TRANSFER_RETRIES = 3
TRANSFER_BACKOFF_S = 0.02

#: prefetch depth for host-resident sources when neither the caller nor the
#: ambient scope says otherwise: classic double buffering (panel i computes
#: while panel i+1 transfers; deeper rings only help jittery links)
DEFAULT_DEPTH = 2

# Thread-local (like the guard's sink stack): the decomposition service
# runs solves on several worker threads, and one thread's ambient depth
# must not reach another thread's concurrent panel walk.
_depth_state = threading.local()


@contextlib.contextmanager
def default_depth(depth: Optional[int]):
    """Ambient prefetch depth for every panel walk in the scope.

    The planner stamps `pipeline_depth` on the ExecutionPlan; executors wrap
    the solve in this scope so duck-typed panel consumers (core/adaptive.py,
    HostOp.matmat) honor the plan without a threaded parameter."""
    prev = getattr(_depth_state, "depth", None)
    _depth_state.depth = depth
    try:
        yield
    finally:
        _depth_state.depth = prev


# Per-thread per-panel callback.  The serve-layer scheduler hangs its
# cooperative yield gate here: a long out-of-core job's panel walk calls the
# hook once per produced panel, and the gate uses those calls to hand the
# device to waiting short requests between panel groups.  Every panel path
# funnels through `_panel_probe`, so the hook covers the staged ring, the
# depth-1 synchronous walk, and `lookahead` alike.  One getattr when unset.
_hook_state = threading.local()


@contextlib.contextmanager
def panel_hook(fn):
    """Ambient per-panel callback for the CURRENT thread's panel walks.

    ``fn(ordinal)`` runs after each panel is produced (post fault/guard/
    validate probes), on the consuming thread — it may block, which is
    exactly how the scheduler's yield gate pauses a big job mid-walk.

    Relationship to `snapshot.boundary` (PR 10): this hook fires on panel
    PRODUCTION (the staging/prefetch side — device-sharing granularity),
    while the engines call the snapshot boundary on panel CONSUMPTION,
    after the panel's contribution is folded into their accumulators —
    the only point where captured state is consistent.  The two funnels
    are deliberately separate: a job parked by the gate holds no snapshot
    lock, and a snapshot save never blocks the gate."""
    prev = getattr(_hook_state, "fn", None)
    _hook_state.fn = fn
    try:
        yield
    finally:
        _hook_state.fn = prev


def resolve_depth(depth: Optional[int] = None, host_resident: bool = False,
                  source_default: Optional[int] = None) -> int:
    """Explicit depth > ambient scope > source attribute > auto.

    The ambient scope outranks `source_default` (an operator's own
    `pipeline_depth` attribute) deliberately: the scope is how an
    ExecutionPlan's budget-clamped depth reaches nested walks, and a
    source preference must not override what the planner decided fits.

    Auto is DEFAULT_DEPTH for host-resident sources on a REAL accelerator
    and 1 everywhere else: on the CPU backend "device" memory is host
    memory — there is no link to overlap, and the staging ring's extra
    panel copies are pure overhead (measured ~1.6x slower end-to-end), so
    prefetch there must be an explicit opt-in (testing the machinery)."""
    if depth:
        return max(1, int(depth))
    override = getattr(_depth_state, "depth", None)
    if override:
        return max(1, int(override))
    if source_default:
        return max(1, int(source_default))
    if host_resident and jax.default_backend() != "cpu":
        return DEFAULT_DEPTH
    return 1


#: jitted identity copy — a fresh device buffer (non-donated jit inputs are
#: never aliased to outputs), used to sever CPU zero-copy device_put aliases
_device_copy = jax.jit(jnp.copy)


def panel_bounds(m: int, b: int) -> List[Tuple[int, int]]:
    """[(lo, hi), ...] covering [0, m) in strides of b (last panel ragged)."""
    if b <= 0:
        raise ValueError(f"panel size must be positive, got {b}")
    return [(lo, min(lo + b, m)) for lo in range(0, m, b)]


def _panel_probe(idx: int, panel, rows: Optional[Tuple[int, int]] = None):
    """Per-produced-panel hook: fault injection, guard finiteness probe,
    `validate=` screen.  One module-global check when everything is off —
    the panel passes through untouched and unread."""
    panel = faults_mod.poison_panel(idx, panel)
    sink = guard_mod.active_sink()
    validating = guard_mod.validation_active()
    if sink is not None or validating:
        # the panel is already device-resident — this is a reduction over
        # bytes the solve was about to read anyway, not an extra pass over A
        finite = jnp.isfinite(panel).all()
        if sink is not None:
            sink.record_panel(idx, finite)
        if validating and not bool(finite):
            where = f"rows {rows[0]}:{rows[1]}" if rows else f"ordinal {idx}"
            raise ValueError(
                f"validate: non-finite values in input panel {idx} ({where}) "
                "— clean the source or drop validate=")
    hook = getattr(_hook_state, "fn", None)
    if hook is not None:
        hook(idx)
    return panel


class _StagingFailed(Exception):
    """Internal: a staged transfer failed after TRANSFER_RETRIES retries —
    the stream degrades to the synchronous walk from this panel on."""

    def __init__(self, idx: int):
        super().__init__(f"staging transfer failed at panel {idx}")
        self.idx = idx


def _await_in_flight(in_flight: List[Optional[jax.Array]]) -> None:
    """Fence every in-flight staged transfer (slot-reuse + early-exit
    safety: called from the stream's ``finally`` so a consumer raising or
    abandoning mid-stream can never leave a DMA reading ring memory)."""
    with trace.span("pipeline.slot_wait"):
        for dev in in_flight:
            if dev is not None:
                dev.block_until_ready()


def _put_with_retry(buf, idx: int) -> jax.Array:
    """`jax.device_put` with bounded retry-with-backoff on transfer errors
    (injected `flaky_link` faults or real runtime transfer failures).
    Raises `_StagingFailed` once the budget is spent."""
    delay = TRANSFER_BACKOFF_S
    for attempt in range(TRANSFER_RETRIES + 1):
        try:
            faults_mod.maybe_fail_transfer(idx)
            return jax.device_put(buf)
        except (faults_mod.TransferError, RuntimeError):
            if attempt == TRANSFER_RETRIES:
                raise _StagingFailed(idx) from None
            guard_mod.note_transfer_retry()
            trace.add("pipeline.transfer_retries")
            time.sleep(delay)
            delay *= 2
    raise _StagingFailed(idx)  # unreachable


def put_panel(host: np.ndarray, idx: int, retry: bool = False) -> jax.Array:
    """Move one host panel to the device: every walk's host panels come
    through here, under a `pipeline.put` span, counted in `pipeline.panels`
    and `pipeline.h2d_bytes` (the bytes that land on the device, staging
    padding included).  ``retry`` (the staged ring) puts with bounded
    retry-with-backoff and may raise `_StagingFailed`; the synchronous walks
    put plainly, as `jnp.asarray` always has."""
    with trace.span("pipeline.put"):
        dev = _put_with_retry(host, idx) if retry else jnp.asarray(host)
    trace.add("pipeline.panels")
    trace.add("pipeline.h2d_bytes", dev.nbytes)
    return dev


def _produce_sync(array, bounds, start: int = 0) -> Iterator[jax.Array]:
    """The synchronous walk over ``bounds[start:]``: each host slice put
    and probed under its `pipeline.panel` span."""
    for i in range(start, len(bounds)):
        lo, hi = bounds[i]
        with trace.span("pipeline.panel"):
            panel = _panel_probe(i, put_panel(array[lo:hi], i), rows=(lo, hi))
        yield panel


def stream_host_panels(
    array,
    bounds: Sequence[Tuple[int, int]],
    depth: int,
) -> Iterator[jax.Array]:
    """Device panels ``array[lo:hi]`` with `depth`-deep staged prefetch.

    A ring of `depth` reusable host staging buffers, each sized to the
    LARGEST panel (the tail is zero-padded up to it, so every
    `jax.device_put` ships the same uniform shape).  When panel *i* is
    yielded, panels *i+1 .. i+depth-1* are already in flight — jax's async
    dispatch runs those copies while the consumer computes on panel *i*.

    Slot-reuse safety: before a staging buffer is overwritten for panel
    *i+depth*, the device array produced from its PREVIOUS occupant
    (panel *i*) is awaited — by then that transfer finished long ago (the
    consumer is `depth` panels ahead), so the wait is ~free, but it makes
    overwriting the source memory of an in-flight DMA impossible.  On the
    CPU backend `jax.device_put` may ZERO-COPY an aligned host buffer — the
    "transfer" is permanent aliasing, which no await can fence — so there
    each staged panel is chased with an explicit on-device copy
    (`_device_copy`) and the slot wait lands on the copy instead; real
    accelerators DMA host memory and skip the extra hop.

    Yields are bit-identical to ``jnp.asarray(array[lo:hi])``: the pad rows
    are sliced back off on device before the consumer ever sees them.
    """
    bounds = list(bounds)
    if not bounds:
        return
    depth = max(1, min(int(depth), len(bounds)))
    if depth == 1:
        yield from _produce_sync(array, bounds)
        return
    block = max(hi - lo for lo, hi in bounds)
    n = array.shape[1]
    ring = [np.empty((block, n), dtype=array.dtype) for _ in range(depth)]
    in_flight: List[Optional[jax.Array]] = [None] * depth

    # On CPU, device_put of an aligned numpy buffer can alias it outright
    # (no copy ever happens) — reusing the slot would then rewrite panels a
    # consumer still holds.  An explicit device-side copy severs the alias;
    # waiting on the COPY before slot reuse guarantees its read of the
    # (possibly aliased) staging memory is complete.
    chase_copy = jax.default_backend() == "cpu"

    def stage(idx: int) -> jax.Array:
        lo, hi = bounds[idx]
        rows = hi - lo
        slot = idx % depth
        with trace.span("pipeline.panel"):
            prev = in_flight[slot]
            if prev is not None:
                # DMA/copy out of this slot must be done
                with trace.span("pipeline.slot_wait"):
                    prev.block_until_ready()
            buf = ring[slot]
            buf[:rows] = array[lo:hi]
            if rows < block:
                buf[rows:] = 0  # uniform transfer shape, jit-stable
            faults_mod.corrupt_staged(idx, buf[:rows])
            dev = put_panel(buf, idx, retry=True)
            if chase_copy:
                dev = _device_copy(dev)
            in_flight[slot] = dev
            panel = dev if rows == block else dev[:rows]
            return _panel_probe(idx, panel, rows=(lo, hi))

    fallback_from: Optional[int] = None
    pending: collections.deque = collections.deque()
    try:
        for i in range(depth):
            try:
                pending.append(stage(i))
            except _StagingFailed as fail:
                fallback_from = fail.idx
                break
        nxt = depth
        while pending:
            panel = pending.popleft()
            if fallback_from is None and nxt < len(bounds):
                # issue the NEXT transfer before handing back control, so it
                # overlaps the consumer's compute on this panel
                try:
                    pending.append(stage(nxt))
                except _StagingFailed as fail:
                    fallback_from = fail.idx
                nxt += 1
            yield panel
        if fallback_from is not None:
            # the link stayed down through the retry budget: finish the walk
            # synchronously (same values, no overlap) instead of failing
            guard_mod.note_transfer_degraded()
            yield from _produce_sync(array, bounds, fallback_from)
    finally:
        _await_in_flight(in_flight)


def lookahead(panels: Iterable, depth: int) -> Iterator:
    """Pull up to `depth - 1` panels ahead of the consumer.

    The generic prefetch for sources whose panels are PRODUCED rather than
    copied (device-resident slices, composed per-panel transforms over an
    already-prefetched base): pulling enqueues the producer's async work,
    which then overlaps the consumer's compute on earlier panels.  Depth 1
    degrades to plain iteration — exactly the pre-pipeline behavior.

    Each pulled panel passes `_panel_probe` at production (fault hooks,
    guard finiteness probe, `validate=` screen) — free when all three are
    inactive."""
    if depth <= 1:
        for i, panel in enumerate(panels):
            yield _panel_probe(i, panel)
        return
    queue: collections.deque = collections.deque()
    for i, panel in enumerate(panels):
        queue.append(_panel_probe(i, panel))
        if len(queue) >= depth:
            yield queue.popleft()
    while queue:
        yield queue.popleft()
