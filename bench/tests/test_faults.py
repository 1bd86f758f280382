"""A run whose timed path is broken must come out as not correct.

Each case runs the harness on the CPU at a tiny size with the check for a
chip skipped and one fault planted under `repro.linalg.decompose`:

- stale: every call returns the first call's factors (a step that returns
  its state unchanged);
- half_rows: the solve sees only the first half of A's rows, the rest read
  as zero (half of the batch left out);
- altered: the singular values are off by 1% where they are produced;
- lost_direction: the last right singular vector comes back as a copy of
  the first (V^T no longer orthonormal; the residual barely moves);
- one_pass_qr, gram_small_svd: the program's own cheaper paths, one-pass
  CholeskyQR for its range finder and the Gram eigendecomposition for its
  small SVD (U, and V, no longer orthonormal);
- memo: every matrix's first result is kept and returned again for it,
  whatever the sketch seed (no cache may serve a call: the check holds two
  solves of one matrix to differ).

The open-loop cell has the same faults planted behind
`DecompositionService.submit` (stale, half_rows, altered, memo), and one of
its own: misrouted, every request resolved with the factors of the pool's
next matrix (a coalesced batch handing its slices to the wrong members).
A request that never resolves is a failure of its own: it counts in
`failed` and the run is not correct.

A cell under a tolerance spec has faults of its own: short_rank, half of
the returned components dropped (too small a rank); loose, the solve run
to ten times the tolerance (a residual over it); overshoot, to a tenth of
it (far more rank than the tolerance needs); and altered, as above.
"""
from __future__ import annotations

import pytest

from test_rehearsal import TOLERANCE, TOLERANCE_LIMITS, make_checkout, run_cell

WRAP = """
import jax, jax.numpy as jnp, numpy as np
from repro import linalg
_real = linalg.decompose
"""

#: the program's own default plan with one field of its configuration
#: changed, to a cheaper path of its own
VARIANT = """
import dataclasses
from repro.linalg import planner
def broken(a, spec, **kw):
    op = linalg.as_linop(a)
    cfg = planner._default_config(op, planner._pick_path(op, None),
                                  planner.Budget.default())
    return _real(a, spec, overrides=dataclasses.replace(cfg, %s=%r), **kw)
linalg.decompose = broken
"""

FAULTS = {
    "stale": WRAP + """
_first = []
def broken(a, spec, **kw):
    if not _first:
        _first.append(_real(a, spec, **kw))
    return _first[0]
linalg.decompose = broken
""",
    "half_rows": WRAP + """
def broken(a, spec, **kw):
    half = a.shape[0] // 2
    if isinstance(a, np.ndarray):
        a = np.concatenate([a[:half], np.zeros_like(a[half:])])
    else:
        a = jnp.asarray(a).at[half:].set(0)
    return _real(a, spec, **kw)
linalg.decompose = broken
""",
    "altered": WRAP + """
import dataclasses
def broken(a, spec, **kw):
    dec = _real(a, spec, **kw)
    U, S, Vt = dec.factors
    return dataclasses.replace(dec, factors=(U, S * 1.01, Vt))
linalg.decompose = broken
""",
    "lost_direction": WRAP + """
import dataclasses
def broken(a, spec, **kw):
    dec = _real(a, spec, **kw)
    U, S, Vt = dec.factors
    return dataclasses.replace(dec, factors=(U, S, Vt.at[-1].set(Vt[0])))
linalg.decompose = broken
""",
    "one_pass_qr": WRAP + VARIANT % ("qr_method", "cqr"),
    "gram_small_svd": WRAP + VARIANT % ("small_svd", "gram"),
    "memo": WRAP + """
_kept = {}
def broken(a, spec, **kw):
    if id(a) not in _kept:
        _kept[id(a)] = _real(a, spec, **kw)
    return _kept[id(a)]
linalg.decompose = broken
""",
}

TOLERANCE_FAULTS = {
    "short_rank": WRAP + """
import dataclasses
def broken(a, spec, **kw):
    dec = _real(a, spec, **kw)
    U, S, Vt = dec.factors
    r = max(1, S.shape[0] // 2)
    return dataclasses.replace(dec, factors=(U[:, :r], S[:r], Vt[:r]))
linalg.decompose = broken
""",
    "loose": WRAP + """
def broken(a, spec, **kw):
    return _real(a, linalg.Tolerance(eps=spec.eps * 10), **kw)
linalg.decompose = broken
""",
    "overshoot": WRAP + """
def broken(a, spec, **kw):
    return _real(a, linalg.Tolerance(eps=spec.eps / 10), **kw)
linalg.decompose = broken
""",
    "altered": FAULTS["altered"],
}


#: the service's submit, wrapped: `_resolve_with(inner, fn)` is a future
#: that resolves with fn(inner's result)
SERVICE = """
import dataclasses
from concurrent.futures import Future
import jax.numpy as jnp
from repro.serve import decomp
_real = decomp.DecompositionService.submit
def _resolve_with(inner, fn):
    outer = Future()
    inner.add_done_callback(lambda f: outer.set_result(fn(f.result())))
    return outer
"""

SERVICE_FAULTS = {
    "stale": SERVICE + """
_first = []
def broken(self, a, spec, **kw):
    if not _first:
        _first.append(_real(self, a, spec, **kw))
    return _first[0]
decomp.DecompositionService.submit = broken
""",
    "half_rows": SERVICE + """
def broken(self, a, spec, **kw):
    return _real(self, jnp.asarray(a).at[a.shape[0] // 2:].set(0), spec, **kw)
decomp.DecompositionService.submit = broken
""",
    "altered": SERVICE + """
def altered(dec):
    U, S, Vt = dec.factors
    return dataclasses.replace(dec, factors=(U, S * 1.01, Vt))
def broken(self, a, spec, **kw):
    return _resolve_with(_real(self, a, spec, **kw), altered)
decomp.DecompositionService.submit = broken
""",
    "memo": SERVICE + """
_kept = {}
def broken(self, a, spec, **kw):
    if id(a) not in _kept:
        _kept[id(a)] = _real(self, a, spec, **kw)
    return _kept[id(a)]
decomp.DecompositionService.submit = broken
""",
    "misrouted": SERVICE + """
_seen = []
def broken(self, a, spec, **kw):
    if not any(b is a for b in _seen):
        _seen.append(a)
    nxt = _seen[([id(b) for b in _seen].index(id(a)) + 1) % len(_seen)]
    return _real(self, nxt, spec, **kw)
decomp.DecompositionService.submit = broken
""",
}


@pytest.mark.parametrize("fault", sorted(SERVICE_FAULTS))
def test_service_fault_is_not_correct(tmp_path, fault):
    root = make_checkout(tmp_path, "service")
    result = run_cell(root, "tiny.service", patch=SERVICE_FAULTS[fault])
    assert result["failed"] == 0
    assert not result["correct"], result["check"]


def test_request_that_never_resolves_fails(tmp_path):
    """One window arrival's future never resolves (set-up makes 15 calls):
    after the drain it counts in `failed`, and the run is not correct."""
    root = make_checkout(tmp_path, "service", drain_s=1.0)
    patch = SERVICE + """
_calls = [0]
def broken(self, a, spec, **kw):
    _calls[0] += 1
    return Future() if _calls[0] == 20 else _real(self, a, spec, **kw)
decomp.DecompositionService.submit = broken
"""
    result = run_cell(root, "tiny.service", patch=patch)
    assert result["failed"] == 1
    assert not result["correct"]


@pytest.mark.parametrize("fault,residency", [
    ("stale", "device"), ("half_rows", "device"), ("altered", "device"),
    ("lost_direction", "device"), ("one_pass_qr", "device"),
    ("gram_small_svd", "device"), ("memo", "device"), ("half_rows", "host"),
    ("gram_small_svd", "host"), ("memo", "host")])
def test_fault_is_not_correct(tmp_path, fault, residency):
    root = make_checkout(tmp_path, residency)
    result = run_cell(root, f"tiny.{residency}", patch=FAULTS[fault])
    assert result["failed"] == 0
    assert not result["correct"], result["check"]


@pytest.mark.parametrize("fault", sorted(TOLERANCE_FAULTS))
def test_tolerance_fault_is_not_correct(tmp_path, fault):
    root = make_checkout(tmp_path, "device", limits=TOLERANCE_LIMITS,
                         config={"spec": TOLERANCE})
    result = run_cell(root, "tiny.device", patch=TOLERANCE_FAULTS[fault])
    assert result["failed"] == 0
    assert not result["correct"], result["check"]
