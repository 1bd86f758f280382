#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans and scopes.

    python3 bench/breakdown.py --workload <cell> --seed <n> --seconds <s> [--out DIR]

runs the cell's set-up and a traced window as bench/run.py does, keeps the
profiler's `.xplane.pb` under --out, and prints one JSON line: the device
ops and the idle gaps of the window named by what the program was doing,
the busy time under each `rsvd.*` device scope, the count, the time and the
share of idle time under each program span, the program's counters over the window,
and the numbers a per-layer metric would read from them.

Names.  An idle gap is named by the benchmark's span covering most of it
(`call`, `wait`, `next_source`, `submit`, `sleep`, as trace_reduce.py
names it), then by the innermost program span (linalg/trace.py) that
covers at least half of it:
`call/pipeline.put`.  A device op is named by its innermost `rsvd.*` scope
(core/rsvd.py), read from the `tf_op` stat that a TPU trace gives each op's
metadata (or, for a `while` that has none, shared by the ops nested in
it), then by its HLO name: `rsvd.small_svd/while.13`.  A trace
without program spans or scopes names everything as trace_reduce.py does.

`jax.profiler.ProfileData` does not expose event-metadata stats, so the
device planes are read here with a walk over the protobuf wire format:
XSpace field 1 holds the planes; XPlane field 2 its name, 3 its lines, 4
its event metadata and 5 its stat metadata (both maps: key 1, value 2);
XLine field 2 its name, 3 its timestamp in ns, 4 its events; XEvent field 1
its metadata id, 2 its offset and 3 its duration, in ps; XEventMetadata
field 2 its name and 5 its stats; XStat field 1 its metadata id, 5 a string
value, 7 a reference to a stat metadata whose name is the string.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import trace_reduce as tr  # noqa: E402

PROGRAM_SPANS = ("linalg.", "engine.", "pipeline.")
SCOPE_PREFIX = "rsvd."
TF_OP = "tf_op"


# ---- the protobuf wire format -------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def fields(buf):
    """(field number, value) of one message: an int for varints, a
    memoryview for length-delimited fields and fixed-width ones."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, value


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for number, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _named(buf) -> str:
    return next((bytes(v).decode("utf-8", "replace")
                 for number, v in fields(buf) if number == 2), "")


def _tf_op(meta, stat_names: dict) -> str | None:
    for number, stat in fields(meta):
        if number != 5:
            continue
        parts = dict(fields(stat))
        if stat_names.get(parts.get(1)) != TF_OP:
            continue
        if 5 in parts:
            return bytes(parts[5]).decode("utf-8", "replace")
        if 7 in parts:
            return stat_names.get(parts[7])
    return None


def read_device_ops(path) -> dict:
    """{chip plane: [(op name, tf_op or None, start_s, end_s), ...]} from
    the `XLA Ops` lines of the device planes."""
    chips = {}
    for number, plane in fields(Path(path).read_bytes()):
        if number != 1:
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for pnum, v in fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                key, meta = _map_entry(v)
                metas[key] = meta
            elif pnum == 5:
                key, meta = _map_entry(v)
                stat_names[key] = _named(meta)
        if not tr.DEVICE_PLANE.match(name):
            continue
        ops, described = [], {}
        for line in lines:
            parts = list(fields(line))
            if next((bytes(v).decode() for n, v in parts if n == 2), "") != tr.OPS_LINE:
                continue
            t0_ps = 1000 * next((v for n, v in parts if n == 3), 0)
            for n, event in parts:
                if n != 4:
                    continue
                ev = dict(fields(event))
                mid = ev.get(1, 0)
                if mid not in described:
                    meta = metas.get(mid, b"")
                    described[mid] = (tr.op_name(_named(meta)),
                                      _tf_op(meta, stat_names))
                start = t0_ps + ev.get(2, 0)
                ops.append((*described[mid], start * 1e-12,
                            (start + ev.get(3, 0)) * 1e-12))
        if ops:
            chips[name] = ops
    return chips


def read_host_spans(path) -> list:
    """[(name, start_s, end_s)] of every span on the host planes."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                          for ev in line.events]
    return spans


# ---- the reduction --------------------------------------------------------------

def scopes_of(tf_op: str | None) -> list:
    """The `rsvd.*` scopes in a jax name stack, outermost first."""
    if not tf_op:
        return []
    return [p for p in tf_op.rstrip(":").split("/") if p.startswith(SCOPE_PREFIX)]


def nested_scopes(ops: list) -> list:
    """The `rsvd.*` scopes of each op of one chip: those of its own name
    stack, or, for an op that has none (a TPU trace gives control flow such
    as `while` no `tf_op`), those that every scoped op nested inside it in
    time shares."""
    own = [scopes_of(tf_op) for _, tf_op, _, _ in ops]
    order = sorted(range(len(ops)), key=lambda i: ops[i][2])
    starts = [ops[i][2] for i in order]
    out = list(own)
    for i, (_, _, s, e) in enumerate(ops):
        if own[i] or e <= s:
            continue
        common = None
        for j in order[bisect.bisect_left(starts, s):bisect.bisect_right(starts, e)]:
            if j == i or ops[j][3] > e or not own[j]:
                continue
            if common is None:
                common = own[j]
            else:
                k = 0
                while k < min(len(common), len(own[j])) and common[k] == own[j][k]:
                    k += 1
                common = common[:k]
            if not common:
                break
        out[i] = common or []
    return out


def op_label(name: str, scopes: list) -> str:
    return f"{scopes[-1]}/{name}" if scopes else name


def innermost_span(interval, spans) -> str | None:
    """The shortest program span that covers at least half of `interval`."""
    half = 0.5 * (interval[1] - interval[0])
    best, best_len = None, None
    for name, s, e in spans:
        if tr.overlap(interval, (s, e)) >= half and (best is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def gap_label(gap, bench_spans, program_spans) -> str:
    outer = tr.name_gap(gap, bench_spans)
    inner = innermost_span(gap, program_spans)
    return f"{outer}/{inner}" if inner else outer


def union_s(intervals, lo: float, hi: float) -> float:
    clipped = [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]
    return sum(e - s for s, e in tr.merge(clipped))


def intersect_s(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        out += tr.overlap(a[i], b[j])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def scope_busy_s(chips: dict, scopes: dict, lo: float, hi: float) -> dict:
    """For each `rsvd.*` scope, the union of the intervals of the ops that
    it holds (`scopes`: each chip's `nested_scopes`), averaged over the
    chips: nested ops (a `while` and its body) count once."""
    out = defaultdict(float)
    for chip, ops in chips.items():
        by_scope = defaultdict(list)
        for (_, _, s, e), held in zip(ops, scopes[chip]):
            for scope in set(held):
                by_scope[scope].append((s, e))
        for scope, intervals in by_scope.items():
            out[scope] += union_s(intervals, lo, hi) / len(chips)
    return dict(out)


def frontend_s(program_spans) -> float | None:
    """Mean per solve of `linalg.decompose` time outside `linalg.execute`."""
    calls = [(s, e) for n, s, e in program_spans if n == "linalg.decompose"]
    execs = tr.merge([(s, e) for n, s, e in program_spans if n == "linalg.execute"])
    if not calls:
        return None
    return sum((e - s) - intersect_s([(s, e)], execs) for s, e in calls) / len(calls)


def reduce(chips: dict, spans: list) -> dict | None:
    """The breakdown of the `window` span of a trace: `chips` as
    read_device_ops gives them, `spans` as read_host_spans does."""
    windows = [(s, e) for n, s, e in spans if n == tr.WINDOW_SPAN]
    if not windows or not chips:
        return None
    lo, hi = windows[0]
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in spans
              if e > lo and s < hi]
    bench_spans = [x for x in inside if x[0] in tr.HOST_SPANS]
    program = [x for x in inside if x[0].startswith(PROGRAM_SPANS)]
    base = tr.reduce_events({c: [(n, s, e) for n, _, s, e in ops]
                             for c, ops in chips.items()}, spans)
    scopes = {chip: nested_scopes(ops) for chip, ops in chips.items()}
    op_s, every = defaultdict(float), []
    for chip, ops in chips.items():
        for (name, _, s, e), held in zip(ops, scopes[chip]):
            if e > lo and s < hi:
                op_s[op_label(name, held)] += (min(e, hi) - max(s, lo)) / len(chips)
                every.append((max(s, lo), min(e, hi)))
    idle = tr.gaps(tr.merge(every), lo, hi)
    idle_s = sum(e - s for s, e in idle)
    longest = sorted(idle, key=lambda g: g[1] - g[0], reverse=True)[:tr.TOP]
    names = sorted({n for n, _, _ in program})
    span_s, span_n, idle_under = {}, {}, {}
    for name in names:
        own = [(s, e) for n, s, e in program if n == name]
        merged = tr.merge(own)
        span_n[name] = len(own)
        span_s[name] = sum(e - s for s, e in merged)
        idle_under[name] = intersect_s(idle, merged) / idle_s if idle_s else 0.0
    solves = sum(1 for n, _, _ in program if n == "linalg.decompose")
    front = frontend_s(program)
    busy_by_scope = scope_busy_s(chips, scopes, lo, hi)
    return {
        "window_s": base["window_s"],
        "busy_s": base["busy_s"],
        "idle_share": base["idle_share"],
        "solves": solves,
        "device_ops": sorted(([n, t] for n, t in op_s.items()),
                             key=lambda x: x[1], reverse=True)[:tr.TOP],
        "idle_gaps": [[gap_label(g, bench_spans, program), g[1] - g[0]]
                      for g in longest],
        "scope_busy_s": busy_by_scope,
        "span_s": span_s,
        "span_count": span_n,
        "idle_share_under_span": idle_under,
        "staging_share": (100.0 * span_s["pipeline.panel"] / base["window_s"]
                          if "pipeline.panel" in span_s else None),
        "frontend_ms": None if front is None else 1e3 * front,
        "small_svd_share": (100.0 * busy_by_scope["rsvd.small_svd"] / base["busy_s"]
                            if "rsvd.small_svd" in busy_by_scope and base["busy_s"] > 0
                            else None),
    }


def reduce_trace(path) -> dict | None:
    return reduce(read_device_ops(path), read_host_spans(path))


# ---- the command ----------------------------------------------------------------

def main(argv=None) -> int:
    import argparse

    import jax

    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", type=Path, default=run.ROOT / "chiprun_out" / "breakdown")
    args = ap.parse_args(argv)
    spec = run.load_cell(run.ROOT, args.workload)
    cfg, traffic, kind = spec["config"], spec["traffic"], spec["kind"]
    run.enable_compile_cache(run.ROOT)
    devices = jax.devices()[:spec["cell"]["chips"]]
    device = devices[0]
    pool = kind.build_pool(cfg, traffic, args.seed, devices)
    if traffic["loop"] == "open":
        import openloop

        loop = openloop.OpenLoop(pool, cfg, traffic, args.seed)
    else:
        loop = run.ClosedLoop(pool, cfg, traffic, args.seed)

    from repro.linalg import trace

    log_dir = args.out / args.workload
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    before = trace.counters()
    try:
        loop.window(args.seconds)
    finally:
        after = trace.counters()
        jax.profiler.stop_trace()
        loop.close()
    xplane = tr.find_xplane(log_dir)
    out = reduce_trace(xplane) or {}
    delta = {n: v - before.get(n, 0) for n, v in after.items()
             if v != before.get(n, 0)}
    solves = delta.get("linalg.solves")
    pass_bytes = kind.required_work(cfg)["bytes"] // (cfg["power_iters"] + 1)
    out.update(device=device.device_kind, xplane=str(xplane), counters=delta,
               attempted=loop.attempted,
               h2d_passes=(delta.get("pipeline.h2d_bytes", 0) / solves / pass_bytes
                           if solves else None))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
