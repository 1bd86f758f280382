"""Jaxpr/HLO contract checker: machine-checked traffic/tracing invariants.

Layer 2 of `repro.analysis` (DESIGN.md §Static analysis).  Given any
`ExecutionPlan`, the checker abstractly traces the solve the plan's path
actually executes (ShapeDtypeStruct inputs — nothing is allocated except
the deliberately tiny concrete re-trace probe for batched plans) and
asserts the contracts the roofline model prices:

  C1 peak-intermediate   no materialized intermediate may reach m x n bytes
                         on matfree/sparse paths (and never exceed the
                         input residency on dense/batched/sharded); streamed
                         plans are checked statically — the plan's device
                         working set (staging panels + sketch-width state)
                         must undercut dense residency.
  C2 donation            the per-panel update steps (`blocked._add_donated`,
                         `_accum_xty`, `_gram_accum`, `adaptive._deflate_step`)
                         really alias their accumulator buffer in compiled
                         HLO — alias bytes == accumulator bytes, exactly.
  C3 row-panel-fallback  the generic `LinOp.row_panels` fallback (offset-
                         diagonal basis slices) lowers with NO gather /
                         scatter primitives.
  C4 reads-of-A          the number of A-touching contractions in the traced
                         jaxpr equals the pass count `rsvd_model` charges
                         for: 1+q fused, 2+2q unfused (sparse: SpMM count).
  C5 trace-accounting    a second identical batched solve must not re-trace
                         (`blocked.trace_count` moves by at most one per
                         plan, then stays put).

Tracing is tag-based: each traced input is tagged, view primitives
(transpose/reshape/...) propagate tags, and everything untagged that an
equation produces counts as a materialized intermediate.  `A.T` therefore
does not count as an m x n intermediate (XLA folds the transpose into
dot_general dimension numbers), while an actual densified copy does.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: primitives that constitute a "read" of their operands for traffic
#: accounting (a GEMM, a fused Pallas kernel, a BCOO SpMM).
MATMUL_PRIMS = {"dot_general", "pallas_call", "bcoo_dot_general"}
#: size-preserving relabelings of an existing buffer — tag-transparent.
VIEW_PRIMS = {"transpose", "reshape", "squeeze", "expand_dims", "rev"}
#: layout staging: `pad` to the Pallas tile quantum produces "A in padded
#: layout" — its reads are charged to the operand, and the staged copy is
#: input residency, not a derived intermediate (first-operand tag flows).
STAGING_PRIMS = {"pad"}
#: call-like primitives whose sub-jaxpr invars match the eqn invars
#: positionally, letting tags flow through.
CALL_PRIMS = {"jit", "custom_jvp_call", "custom_vjp_call", "closed_call",
              "remat2", "checkpoint", "shard_map", "custom_vjp_call_jaxpr"}


class ContractViolation(AssertionError):
    """One or more plan contracts failed; `.results` carries the details."""

    def __init__(self, results: List["ContractResult"]):
        self.results = results
        bad = [r for r in results if not r.ok]
        super().__init__(
            "; ".join(f"{r.contract}[{r.plan_label}]: {r.detail}" for r in bad))


@dataclasses.dataclass(frozen=True)
class ContractResult:
    contract: str
    plan_label: str
    ok: bool
    detail: str


@dataclasses.dataclass(frozen=True)
class JaxprFacts:
    """What the tag-propagating jaxpr walk measured."""

    peak_intermediate_bytes: int
    reads: Dict[str, int]          # tag -> A-touching contraction count
    prim_counts: Dict[str, int]    # primitive name -> occurrences (recursive)

    def count(self, prim: str) -> int:
        return self.prim_counts.get(prim, 0)


# ---------------------------------------------------------------------------
# Tag-propagating jaxpr analysis
# ---------------------------------------------------------------------------

def _open_jaxpr(obj):
    """Duck-typed: ClosedJaxpr -> .jaxpr, open Jaxpr -> itself, else None.
    (shard_map carries an *open* jaxpr param; jit a ClosedJaxpr.)"""
    if hasattr(obj, "jaxpr") and hasattr(obj, "consts"):
        return obj.jaxpr
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    return None


def _is_var(atom) -> bool:
    return not hasattr(atom, "val")  # Literals carry .val, Vars do not


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for d in shape:
        size *= int(d)
    return size * jnp.dtype(dtype).itemsize


def _sub_jaxprs(eqn) -> List:
    subs = []
    for value in eqn.params.values():
        opened = _open_jaxpr(value)
        if opened is not None:
            subs.append(opened)
        elif isinstance(value, (tuple, list)):
            subs.extend(o for o in (_open_jaxpr(v) for v in value)
                        if o is not None)
    return subs


def _analyze(jaxpr, in_tags: Sequence[frozenset], facts: dict) -> List[frozenset]:
    """Walk one (open) jaxpr, threading input tags; returns outvar tags.

    `facts` accumulates {"peak": int, "reads": Counter-ish, "prims": dict}.
    """
    tags: Dict[object, frozenset] = {}
    for var, tag in zip(jaxpr.invars, in_tags):
        tags[var] = tag
    for cv in jaxpr.constvars:
        facts["peak"] = max(facts["peak"], _aval_bytes(cv.aval))
    empty = frozenset()

    def tag_of(atom) -> frozenset:
        return tags.get(atom, empty) if _is_var(atom) else empty

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        facts["prims"][name] = facts["prims"].get(name, 0) + 1
        eqn_in_tags = [tag_of(v) for v in eqn.invars]
        union: frozenset = empty
        for t in eqn_in_tags:
            union = union | t
        if name in MATMUL_PRIMS:
            for t in union:
                facts["reads"][t] = facts["reads"].get(t, 0) + 1
        out_tags: Optional[List[frozenset]] = None
        if name != "pallas_call":  # pallas params hold block-level jaxprs
            for sub in _sub_jaxprs(eqn):
                if name in CALL_PRIMS and len(sub.invars) == len(eqn.invars):
                    sub_out = _analyze(sub, eqn_in_tags, facts)
                    if len(sub_out) == len(eqn.outvars):
                        out_tags = sub_out
                else:
                    _analyze(sub, [empty] * len(sub.invars), facts)
        if out_tags is None:
            view = ((name in VIEW_PRIMS and len(eqn.invars) == 1)
                    or name in STAGING_PRIMS)
            out_tags = [eqn_in_tags[0] if view else empty
                        for _ in eqn.outvars]
        for var, tag in zip(eqn.outvars, out_tags):
            tags[var] = tag
            if not tag:
                facts["peak"] = max(facts["peak"], _aval_bytes(var.aval))
    return [tag_of(v) for v in jaxpr.outvars]


def trace_facts(fn: Callable, args: Sequence,
                tag_positions: Dict[int, str]) -> JaxprFacts:
    """Abstractly trace fn(*args) and measure peak intermediates + per-tag
    contraction reads.  `tag_positions` maps argument index -> tag name
    (typically {0: "A"})."""
    closed = jax.make_jaxpr(fn)(*args)
    in_tags = [frozenset([tag_positions[i]]) if i in tag_positions
               else frozenset() for i in range(len(closed.jaxpr.invars))]
    facts = {"peak": 0, "reads": {}, "prims": {}}
    _analyze(closed.jaxpr, in_tags, facts)
    return JaxprFacts(facts["peak"], dict(facts["reads"]),
                      dict(facts["prims"]))


# ---------------------------------------------------------------------------
# Individual contract verifiers (negative tests drive these directly)
# ---------------------------------------------------------------------------

def verify_peak(facts: JaxprFacts, bound_bytes: int) -> Tuple[bool, str]:
    ok = facts.peak_intermediate_bytes <= bound_bytes
    return ok, (f"peak materialized intermediate "
                f"{facts.peak_intermediate_bytes}B vs bound {bound_bytes}B")


def verify_reads(facts: JaxprFacts, expected: int,
                 tag: str = "A") -> Tuple[bool, str]:
    got = facts.reads.get(tag, 0)
    return got == expected, f"reads of {tag}: traced {got}, model says {expected}"


def verify_sparse_reads(facts: JaxprFacts, expected: int) -> Tuple[bool, str]:
    """Sparse transposition re-packs data/indices, which legitimately drops
    the tag — every BCOO contraction in a sparse solve IS a read of A, so
    the primitive count is the honest tally."""
    got = facts.count("bcoo_dot_general")
    return got == expected, (f"SpMM reads of A: traced {got} "
                             f"bcoo_dot_general, model says {expected}")


def verify_donation(jitted, args, acc_bytes: int, **kwargs) -> Tuple[bool, str]:
    compiled = jitted.lower(*args, **kwargs).compile()
    alias = compiled.memory_analysis().alias_size_in_bytes
    return alias == acc_bytes, (f"aliased {alias}B, accumulator is "
                                f"{acc_bytes}B (must match exactly)")


def verify_no_gather_scatter(fn: Callable, args: Sequence) -> Tuple[bool, str]:
    facts = trace_facts(fn, args, {})
    bad = sorted(p for p in facts.prim_counts
                 if "gather" in p or "scatter" in p)
    return not bad, (f"gather/scatter primitives in panel fallback: {bad}"
                     if bad else "no gather/scatter primitives")


def verify_no_retrace(solve: Callable, count: Callable[[], int]) -> Tuple[bool, str]:
    """Run `solve` twice; the trace tally may move at most once on the first
    call and must not move on the second."""
    before = count()
    solve()
    first = count() - before
    solve()
    second = count() - before - first
    ok = first <= 1 and second == 0
    return ok, (f"trace delta first call {first}, second call {second} "
                "(must be <=1 then 0)")


# ---------------------------------------------------------------------------
# Plan-level checks
# ---------------------------------------------------------------------------

def expected_reads_of_a(pl) -> int:
    """`rsvd_model` pass counts: 1+q with the fused power step, else 2+2q
    (sketch + two per stabilized/plain iteration + projection)."""
    q = int(pl.power_iters)
    return (1 + q) if pl.fused_power else (2 + 2 * q)


def intermediate_bound_bytes(pl) -> int:
    """C1 bound.  Matrix-free/sparse paths must stay strictly below ever
    materializing A; in-core paths must never exceed input residency."""
    itemsize = jnp.dtype(pl.dtype).itemsize
    mn = int(pl.m) * int(pl.n) * itemsize
    if pl.path in ("matfree", "sparse"):
        return mn - 1
    if pl.path == "batched":
        return int(pl.batch) * mn
    return mn


def streamed_working_set_bytes(pl) -> int:
    """Device residency of a streamed plan: staged panels (pipeline depth of
    them) plus the sketch-width state (Y m x s, Z/B n x s, Gram s x s)."""
    itemsize = jnp.dtype(pl.dtype).itemsize
    depth = max(1, int(pl.pipeline_depth or 1))
    panels = depth * int(pl.block_rows) * int(pl.n) * itemsize
    state = (int(pl.m) * int(pl.s) + 2 * int(pl.n) * int(pl.s)
             + 2 * int(pl.s) * int(pl.s)) * itemsize
    return panels + state


def _seed_sds():
    return jax.ShapeDtypeStruct((), jnp.uint32)


def _guard_wrap(pl, body: Callable) -> Callable:
    """Under guard report/retry the body traces with an open probe sink —
    the contract run must mirror that (probes ride the same trace)."""
    if pl.guard is None or pl.guard.mode == "off":
        return body

    def wrapped(*args):
        from repro.linalg import guard as guard_mod

        with guard_mod.collecting():
            return body(*args)

    return wrapped


def _traceable_for(pl, op=None):
    """(fn, args, tag_positions) abstractly tracing what the plan executes,
    or None for paths checked statically (streamed/adaptive)."""
    from repro.core import blocked, qr as qr_mod, rsvd

    dtype = jnp.dtype(pl.dtype)
    m, n, k = int(pl.m), int(pl.n), int(pl.k)
    cfg = pl.to_config()
    if pl.path == "dense":
        def body(A, seed):
            with qr_mod.kernel_backend(cfg.kernel_backend):
                return rsvd._rsvd_body(A, k, cfg, seed)

        return (_guard_wrap(pl, body),
                (jax.ShapeDtypeStruct((m, n), dtype), _seed_sds()), {0: "A"})
    if pl.path == "batched":
        bcfg = blocked.batched_cfg(cfg)

        def body(stack, seeds):
            return blocked._batched_tall_body(stack, seeds, k, bcfg)

        return (_guard_wrap(pl, body),
                (jax.ShapeDtypeStruct((int(pl.batch), m, n), dtype),
                 jax.ShapeDtypeStruct((int(pl.batch),), jnp.uint32)),
                {0: "A"})
    if pl.path == "matfree":
        from repro.linalg import api as api_mod
        from repro.linalg import pipeline as pipeline_mod
        from repro.linalg.operators import CenteredOp, DenseOp

        def body(X, mu, seed):
            with pipeline_mod.default_depth(pl.pipeline_depth):
                return api_mod._matfree_svd(
                    CenteredOp(DenseOp(X), mu), k, pl, seed)

        return (_guard_wrap(pl, body),
                (jax.ShapeDtypeStruct((m, n), dtype),
                 jax.ShapeDtypeStruct((n,), dtype), _seed_sds()), {0: "A"})
    if pl.path == "sparse":
        from jax.experimental import sparse as jsparse

        from repro.linalg import api as api_mod
        from repro.linalg import pipeline as pipeline_mod
        from repro.linalg.operators import SparseOp

        bcoo = op.bcoo if op is not None else _synthetic_bcoo(m, n, dtype)

        def body(data, seed):
            a = jsparse.BCOO((data, bcoo.indices), shape=bcoo.shape)
            with pipeline_mod.default_depth(pl.pipeline_depth):
                return api_mod._matfree_svd(SparseOp(a), k, pl, seed)

        return (_guard_wrap(pl, body),
                (jax.ShapeDtypeStruct(bcoo.data.shape, dtype), _seed_sds()),
                {0: "A"})
    if pl.path == "sharded":
        from repro.core import distributed

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))

        def body(A):
            return distributed.svd_sharded(A, k, mesh, "data", cfg, seed=0)

        return (_guard_wrap(pl, body),
                (jax.ShapeDtypeStruct((m, n), dtype),), {0: "A"})
    return None


def _synthetic_bcoo(m, n, dtype):
    from jax.experimental import sparse as jsparse

    mask = (np.arange(m * n) % 11 == 0).reshape(m, n)
    dense = np.where(mask, 1.0, 0.0).astype(np.dtype(dtype))
    return jsparse.BCOO.fromdense(jnp.asarray(dense))


def _matmat_only_op(X):
    """A protocol-only LinOp (no .array): exercises the generic row_panels
    basis-slice fallback, the codepath C3 pins gather/scatter-free."""
    from repro.linalg.operators import LinOp

    class _MatmatOnly(LinOp):
        @property
        def shape(self):
            return tuple(X.shape)

        @property
        def dtype(self):
            return X.dtype

        def matmat(self, B):
            return X @ B

        def rmatmat(self, Y):
            return X.T @ Y

    return _MatmatOnly()


def _check_donation_suite(pl, label: str) -> List[ContractResult]:
    from repro.core import adaptive, blocked

    dtype = jnp.dtype(pl.dtype)
    s = max(2, int(pl.s))
    b = max(2, min(int(pl.block_rows or 8), 8))
    n = min(int(pl.n), 16)
    m = min(int(pl.m), 32)
    acc = jax.ShapeDtypeStruct((n, s), dtype)
    results = []
    cases = [
        ("blocked._add_donated",
         lambda: verify_donation(blocked._add_donated,
                                 (acc, jax.ShapeDtypeStruct((n, s), dtype)),
                                 n * s * dtype.itemsize)),
        ("blocked._accum_xty",
         lambda: verify_donation(blocked._accum_xty,
                                 (acc, jax.ShapeDtypeStruct((b, n), dtype),
                                  jax.ShapeDtypeStruct((b, s), dtype)),
                                 n * s * dtype.itemsize)),
        ("blocked._gram_accum",
         lambda: verify_donation(blocked._gram_accum,
                                 (jax.ShapeDtypeStruct((s, s), dtype),
                                  jax.ShapeDtypeStruct((b, s), dtype)),
                                 s * s * dtype.itemsize, backend="jnp")),
        ("adaptive._deflate_step",
         lambda: verify_donation(adaptive._deflate_step,
                                 (jax.ShapeDtypeStruct((m, b), dtype),
                                  jax.ShapeDtypeStruct((m, s), dtype)),
                                 m * b * dtype.itemsize)),
    ]
    for name, run in cases:
        ok, detail = run()
        results.append(ContractResult("C2-donation", label, ok,
                                      f"{name}: {detail}"))
    return results


def check_plan_contracts(pl, label: Optional[str] = None,
                         op=None) -> List[ContractResult]:
    """Every contract applicable to this plan's path, as a result list."""
    label = label or f"{pl.path}:{pl.m}x{pl.n}:k{pl.k}:guard-{pl.guard.mode}"
    results: List[ContractResult] = []

    traceable = _traceable_for(pl, op=op)
    if traceable is not None:
        fn, args, tag_positions = traceable
        facts = trace_facts(fn, args, tag_positions)
        ok, detail = verify_peak(facts, intermediate_bound_bytes(pl))
        results.append(ContractResult("C1-peak-intermediate", label, ok, detail))
        if pl.path == "sparse":
            ok, detail = verify_sparse_reads(facts, expected_reads_of_a(pl))
        else:
            ok, detail = verify_reads(facts, expected_reads_of_a(pl))
        results.append(ContractResult("C4-reads-of-a", label, ok, detail))

    if pl.path in ("streamed", "adaptive"):
        ws = streamed_working_set_bytes(pl) if pl.path == "streamed" else None
        if ws is not None:
            dense_bytes = int(pl.m) * int(pl.n) * jnp.dtype(pl.dtype).itemsize
            results.append(ContractResult(
                "C1-peak-intermediate", label, ws < dense_bytes,
                f"streamed device working set {ws}B vs dense residency "
                f"{dense_bytes}B (streaming must undercut it)"))
        results.extend(_check_donation_suite(pl, label))

    if pl.path in ("matfree", "sparse"):
        dtype = jnp.dtype(pl.dtype)
        block = max(2, min(int(pl.m), 8))

        def one_panel(X):
            oper = _matmat_only_op(X)
            for panel in oper.row_panels(block):
                return panel

        ok, detail = verify_no_gather_scatter(
            one_panel,
            (jax.ShapeDtypeStruct((min(int(pl.m), 32), min(int(pl.n), 16)),
                                  dtype),))
        results.append(ContractResult("C3-row-panel-fallback", label, ok,
                                      detail))

    if pl.path == "batched":
        results.append(_check_trace_accounting(pl, label))
    return results


def _check_trace_accounting(pl, label: str) -> ContractResult:
    from repro.core import blocked
    from repro.serve.decomp import cache as serve_cache

    dtype = jnp.dtype(pl.dtype)
    batch, m, n, k = int(pl.batch), int(pl.m), int(pl.n), int(pl.k)
    cfg = pl.to_config()
    # Deterministic filler (counter-RNG-free on purpose): conditioning is
    # irrelevant here, only whether the program re-traces.
    stack = ((jnp.arange(batch * m * n, dtype=jnp.float32)
              .reshape(batch, m, n) * 0.37) % 1.0 + 0.1).astype(dtype)
    seeds = blocked.slice_seeds(0, batch)

    def solve():
        jax.block_until_ready(blocked.svd_batched(stack, k, cfg, seed=seeds))

    ok, detail = verify_no_retrace(solve, lambda: serve_cache.trace_count(pl))
    return ContractResult("C5-trace-accounting", label, ok, detail)


def assert_plan_contracts(pl, label: Optional[str] = None,
                          op=None) -> List[ContractResult]:
    """Pytest-facing entry: raises ContractViolation on any failed contract,
    returns the full result list otherwise."""
    results = check_plan_contracts(pl, label=label, op=op)
    if any(not r.ok for r in results):
        raise ContractViolation(results)
    return results


# ---------------------------------------------------------------------------
# Golden dispatch-table sweep
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SweepReport:
    plans: List[str]
    results: List[ContractResult]

    @property
    def violations(self) -> List[ContractResult]:
        return [r for r in self.results if not r.ok]

    @property
    def ok(self) -> bool:
        return not self.violations


def golden_plan_table() -> List[Tuple[str, object, object]]:
    """(label, plan, op) across every planner path x guard off/report —
    small shapes (plans are shape-only; tracing allocates nothing)."""
    from repro import linalg
    from repro.core.rsvd import RSVDConfig
    from repro.linalg.operators import SparseOp

    def sds(m, n, dt=jnp.float32):
        return jax.ShapeDtypeStruct((m, n), dt)

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    bcoo = _synthetic_bcoo(64, 32, jnp.float32)
    entries = []
    for guard in (None, "report"):
        tag = "off" if guard is None else guard
        cases = [
            (f"dense_faithful_{tag}",
             lambda: linalg.plan(linalg.DenseOp(sds(96, 48)), 8,
                                 guard=guard), None),
            (f"dense_fast_{tag}",
             lambda: linalg.plan(linalg.DenseOp(sds(96, 48)), 8,
                                 overrides=RSVDConfig.fast(), guard=guard),
             None),
            (f"dense_f64_{tag}",
             lambda: linalg.plan(linalg.DenseOp(sds(64, 32, jnp.float64)), 6,
                                 guard=guard), None),
            (f"wide_orientation_{tag}",
             lambda: linalg.plan(linalg.DenseOp(sds(32, 96)), 6,
                                 guard=guard), None),
            (f"streamed_{tag}",
             lambda: linalg.plan(linalg.DenseOp(sds(4096, 128)), 8,
                                 overrides=RSVDConfig.streaming(1024),
                                 guard=guard), None),
            (f"batched_{tag}",
             lambda: linalg.plan(linalg.StackedOp(jnp.zeros((3, 48, 24))), 4,
                                 overrides=RSVDConfig.fast(), guard=guard),
             None),
            (f"sharded_{tag}",
             lambda: linalg.plan(linalg.ShardedOp(sds(128, 32), mesh, "data"),
                                 8, guard=guard), None),
            (f"matfree_{tag}",
             lambda: linalg.plan(
                 linalg.CenteredOp(linalg.DenseOp(sds(96, 48))), 8,
                 guard=guard), None),
            (f"sparse_{tag}",
             lambda: linalg.plan(SparseOp(bcoo), 4, guard=guard),
             SparseOp(bcoo)),
            (f"adaptive_{tag}",
             lambda: linalg.plan(linalg.DenseOp(sds(96, 48)),
                                 linalg.Tolerance(1e-2), guard=guard), None),
        ]
        for label, mk_plan, op in cases:
            entries.append((label, mk_plan(), op))
    return entries


def sweep(entries=None) -> SweepReport:
    """Run every contract over the golden dispatch table (the CLI's
    `--contracts` mode and the CI analysis lane)."""
    entries = golden_plan_table() if entries is None else entries
    results: List[ContractResult] = []
    labels = []
    for label, pl, op in entries:
        labels.append(label)
        results.extend(check_plan_contracts(pl, label=label, op=op))
    return SweepReport(labels, results)


__all__ = [
    "ContractResult", "ContractViolation", "JaxprFacts", "SweepReport",
    "assert_plan_contracts", "check_plan_contracts", "expected_reads_of_a",
    "golden_plan_table", "intermediate_bound_bytes",
    "streamed_working_set_bytes", "sweep", "trace_facts", "verify_donation",
    "verify_no_gather_scatter", "verify_no_retrace", "verify_peak",
    "verify_reads", "verify_sparse_reads",
]
