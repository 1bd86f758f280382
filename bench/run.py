#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(bench/configs/, the matrix and the solve) under a traffic mix
(bench/traffic/<name>.json, read by loadgen.py).  The configuration's kind
(bench/kinds/<kind>.py, found by name, "dense_spectrum" where it names
none) builds the cell's pool of matrices on the cell's devices from
--seed, judges a solve and prices one; sources.py reads the spec each call
passes and the placement over the chips.  The run builds the pool, warms
up every program the window runs (set-up), then measures for --seconds: a
closed loop calls `repro.linalg.decompose`, each call ended by
`block_until_ready` on its factors (ClosedLoop, below); an open loop
submits to `DecompositionService` on a fixed schedule and drains
(openloop.py).

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by bench/metrics/<name>.py.  After the
window, a sample of the window's solves drawn from the seed is judged by
the kind against the plain reference (reference.py) and the limits in
bench/limits/<cell>.json; each compared number is printed beside its limit,
as the last lines on stderr and under "check", the last key of the result.

Without a TPU, with fewer chips than the cell asks for, or on a chip that
peaks.json does not describe, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(ROOT / "src"))

import loadgen  # noqa: E402
import sources  # noqa: E402
import trace_reduce  # noqa: E402

#: past this many seconds the run dumps every thread's stack and exits
#: non-zero, inside the 1200 s a first run (which compiles) is given
WATCHDOG_S = 1100
#: jax's own lowering and XLA-compile events; the compile event covers a
#: persistent-cache read too
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
#: repeats of the host-to-device probe; the median is taken
LINK_PROBES = 8


class NoChip(RuntimeError):
    """The run cannot measure here: no TPU, too few chips, or unknown peaks."""


def _selected(entries: list, cell: str) -> list:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def load_cell(root: Path, name: str) -> dict:
    """Everything the named cell needs, found by name: its entry, its
    configuration file and kind, its traffic file, its limits and its
    metrics.  A configuration whose spec or placement the cell cannot run
    is an error here, before set-up."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / bench["paths"][0]
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    sources.spec(cfg)
    sources.placement(cfg, traffic, cell["chips"])
    return {
        "cell": cell,
        "config": cfg,
        "kind": _module(bench_dir / "kinds", cfg.get("kind", "dense_spectrum"), "kind"),
        "traffic": traffic,
        "limits": json.loads((bench_dir / "limits" / f"{name}.json").read_text()),
        "end_to_end": _selected(bench["end_to_end"], name),
        "per_layer": _selected(bench["per_layer"], name),
        "metrics_dir": bench_dir / "metrics",
    }


def _module(directory: Path, name: str, role: str):
    """The module `<directory>/<name>.py`, loaded under a name of its own."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {role} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{role}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(metrics_dir: Path, name: str):
    return _module(metrics_dir, name, "metric").read


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache, at the checkout's fixed
    `.jax_cache/` whatever the environment says, so that two checkouts
    share nothing; every program is kept, however fast it compiled, so that
    a second run compiles nothing."""
    import jax

    cache_dir = root / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)   # jax writes no entry into a missing one
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileLog:
    """When (host clock) and how long jax lowered and compiled."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.events.append((time.perf_counter(), duration))

    def seconds_between(self, t0: float, t1: float) -> float:
        with self._lock:
            return sum(d for t, d in self.events if t0 <= t <= t1)


def measure_link(host_panel) -> float:
    """Host-to-device bytes per second of a plain `device_put` of a host
    buffer: the median of LINK_PROBES after one warm-up."""
    import jax

    jax.block_until_ready(jax.device_put(host_panel))
    times = []
    for _ in range(LINK_PROBES):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(host_panel))
        times.append(time.perf_counter() - t0)
    times.sort()
    return host_panel.nbytes / times[len(times) // 2]


def solve(linalg, source, spec, seed: int):
    """One call, ended by block_until_ready on all its factors."""
    import jax

    t_call = time.perf_counter()
    with jax.profiler.TraceAnnotation("call"):
        dec = linalg.decompose(source, spec, seed=seed)
    t_return = time.perf_counter()
    with jax.profiler.TraceAnnotation("wait"):
        factors = jax.block_until_ready(tuple(dec.factors))
    return factors, {"t_call": t_call, "t_return": t_return,
                     "t_ready": time.perf_counter()}


@contextmanager
def profiled(enabled: bool):
    """Run the block under the profiler (its log under TMPDIR) and leave the
    reduced trace in the yielded dict's "trace"; None when not enabled."""
    import jax

    out = {"trace": None}
    if not enabled:
        yield out
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        xplane = trace_reduce.find_xplane(log_dir)
        out["trace"] = trace_reduce.reduce_trace(xplane) if xplane else None


class ClosedLoop:
    """One caller that sends the next call to `linalg.decompose` when the
    last one's factors are ready.  Set-up: the link probe of a pool in host
    memory and the traffic's warm-up calls."""

    def __init__(self, pool, cfg: dict, traffic: dict, run_seed: int):
        from repro import linalg

        self.linalg, self.traffic, self.seed = linalg, traffic, run_seed
        self.srcs = sources.program_inputs(linalg, pool)
        self.spec = sources.program_spec(linalg, cfg)
        self.link_bytes_per_s = None
        if traffic["residency"] == "host":
            self.link_bytes_per_s = measure_link(
                pool.members[0][:traffic["link_probe_rows"]])
        self.calls = 0
        self.first = {}   # the first solve of each member, for the repeat check
        for _ in range(traffic["warmup_calls"]):
            member, seed = loadgen.call(traffic, run_seed, self.calls)
            self.first.setdefault(member, solve(linalg, self.srcs[member], self.spec, seed)[0])
            self.calls += 1

    def window(self, seconds: float) -> tuple[float, float]:
        import jax

        linalg, srcs, spec, traffic = self.linalg, self.srcs, self.spec, self.traffic
        calls, first, repeat = self.calls, self.first, None
        records, failed, attempted = [], 0, 0
        sample = loadgen.Reservoir(traffic["check_sample"], self.seed)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            while time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("next_source"):
                    member, seed = loadgen.call(traffic, self.seed, calls)
                    calls += 1
                attempted += 1
                try:
                    factors, rec = solve(linalg, srcs[member], spec, seed)
                except Exception:  # a solve that raises counts in `failed`
                    failed += 1
                    if failed == 1:
                        traceback.print_exc()
                    continue
                records.append(rec)
                sample.offer((member, factors))
                if repeat is None:
                    if member in first:
                        repeat, first = (first[member], factors), {}
                    else:
                        first[member] = factors
        t1 = records[-1]["t_ready"] if records else time.perf_counter()
        self.solves, self.failed, self.attempted = records, failed, attempted
        self.sample, self.repeat = sample.items, repeat
        return t0, t1

    def end_to_end(self, setup_s: float, window_s: float) -> dict:
        return {
            "solves_per_s": len(self.solves) / window_s if self.solves else None,
            "setup_s": setup_s,
        }

    def ctx(self) -> dict:
        return {"link_bytes_per_s": self.link_bytes_per_s}

    def close(self) -> None:
        self.srcs = None


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    spec = load_cell(root, args.workload)
    cfg, traffic, kind = spec["config"], spec["traffic"], spec["kind"]

    import jax

    import reference
    import work

    loadgen.validate(traffic)
    enable_compile_cache(root)
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    least_time_s = None
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU; jax found {devices[0].platform!r}")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips; jax found {len(devices)}")
        peaks = work.peaks_for(devices[0].device_kind)
        least_time_s = work.least_time_s(kind.required_work(cfg), peaks, chips)[0]
    used = devices[:chips]
    compile_log = CompileLog()

    # ---- set-up: the pool, then the loop's own (warm-up of every program) --
    pool = kind.build_pool(cfg, traffic, args.seed, used)
    if traffic["loop"] == "open":
        import openloop

        loop = openloop.OpenLoop(pool, cfg, traffic, args.seed)
    else:
        loop = ClosedLoop(pool, cfg, traffic, args.seed)
    setup_s = time.perf_counter() - T_START

    # ---- the window --------------------------------------------------------
    with profiled(args.trace == 1) as traced:
        t0, t1 = loop.window(args.seconds)
    trace = traced["trace"]
    window_s = t1 - t0
    compile_s = compile_log.seconds_between(t0, t1)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)

    # ---- metrics -----------------------------------------------------------
    metrics = {}
    if args.trace == 0:
        values = loop.end_to_end(setup_s, window_s)
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        if trace is None:
            raise RuntimeError("the traced window holds no device operation")
        ctx = {"solves": loop.solves, "window_s": window_s, "compile_s": compile_s,
               "trace": trace, "least_time_s": least_time_s,
               "pass_bytes": kind.required_work(cfg)["bytes"] // (cfg["power_iters"] + 1),
               **loop.ctx()}
        for m in spec["per_layer"]:
            value = _reader(spec["metrics_dir"], m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- the check, once the window has closed -----------------------------
    loop.close()
    worst = {}
    # by member, so that a kind can keep one member on the device at a time
    for member, factors in sorted(loop.sample, key=lambda it: it[0]):
        for name, v in kind.judge(pool, member, factors, cfg).items():
            worst[name] = max(worst.get(name, v), v)
    if loop.repeat is not None:
        # two calls on one matrix with different sketch seeds: bit-identical
        # factors mean a result was kept and served again
        worst["repeat_identical"] = reference.identical(*loop.repeat)
    # a number that is missing or not finite prints as null and fails
    check = {name: {"value": worst[name] if math.isfinite(worst.get(name, math.inf))
                    else None, "limit": limit}
             for name, limit in spec["limits"].items()}
    correct = (loop.failed == 0 and bool(loop.sample)
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in check.values()))

    result = {
        "correct": correct,
        "attempted": loop.attempted,
        "failed": loop.failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace is not None:
        result["device"].update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["check"] = check
    print(f"set-up compiled for {compile_log.seconds_between(0.0, t0)!r} s", file=sys.stderr)
    print(f"reference numbers, worst of {len(loop.sample)} sampled solves: "
          f"{worst}", file=sys.stderr)
    for name, c in check.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        sys.exit(2)
