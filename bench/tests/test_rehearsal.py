"""A data-only rehearsal of the harness on the CPU, at a tiny size.

A throwaway configuration, traffic mix, limits file and cell (and, for a
kind of matrix of its own, a kind file) are written into a copy of the
benchmark in a temporary directory; the harness finds them by name and runs
them with no code edit.  The command itself refuses to measure on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

TINY_CONFIG = {
    "name": "tiny_2048x256", "source": "rehearsal only",
    "m": 2048, "n": 256, "dtype": "float32", "spectrum": "inverse_square",
    "rank": 20, "oversample": 10, "power_iters": 2,
    "reduced": [], "assumed": [],
}
#: the numbers every cell compares, at limits a sound tiny run keeps
LIMITS = {"residual_ratio": 1.1, "u_orth_err": 1e-4, "v_orth_err": 1e-4,
          "sigma_rel_err_top1": 1e-4, "sigma_rel_err_top10": 1e-3,
          "repeat_identical": 0}

#: a tolerance spec the program meets at the tiny size (its error estimate
#: cannot certify 1e-3 there: PERF.md), and the limits a sound run keeps
TOLERANCE = {"kind": "tolerance", "eps": 1e-2}
TOLERANCE_LIMITS = {"residual_over_eps": 1.0, "rank_over_min": 1.5,
                    "u_orth_err": 1e-4, "v_orth_err": 1e-4,
                    "sigma_rel_err_top1": 1e-4, "sigma_rel_err_top10": 1e-3,
                    "repeat_identical": 0}
#: each new form a configuration can take, as data alone, with the limits a
#: sound tiny run of it keeps: under the slow spectrum a rank-20 sketch with
#: q = 2 reads the leading singular values a few per cent low
FORMS = {
    "sharp": ({"spectrum": "sharp", "beta": 30}, LIMITS),
    "slow": ({"spectrum": "slow"},
             dict(LIMITS, sigma_rel_err_top1=0.2, sigma_rel_err_top10=0.3)),
    "tolerance": ({"spec": TOLERANCE}, TOLERANCE_LIMITS),
}

#: the traffic of each tiny cell, by kind: a closed loop over a pool on the
#: device or in host memory, or an open loop through the service
TINY_TRAFFIC = {
    "device": {"name": "closed.tiny_device", "loop": "closed", "residency": "device",
               "pool": 3, "warmup_calls": 3, "check_sample": 4},
    "host": {"name": "closed.tiny_host", "loop": "closed", "residency": "host",
             "pool": 3, "warmup_calls": 3, "check_sample": 4, "link_probe_rows": 256},
    "service": {"name": "open.tiny_service", "loop": "open", "entry": "service",
                "residency": "device", "pool": 3, "check_sample": 4,
                "arrivals": {"process": "poisson", "rate_per_s": 20.0}, "drain_s": 60},
    "burst": {"name": "open.tiny_burst", "loop": "open", "entry": "service",
              "residency": "device", "pool": 3, "check_sample": 4,
              "arrivals": {"process": "gamma", "rate_per_s": 20.0, "cv": 2.0},
              "drain_s": 60},
}
#: the committed cell whose metrics each kind of tiny cell reports, and the
#: end-to-end metrics it adds: a bursty open loop is reported by the
#: requests it completes as well as by its tail
TEMPLATE = {"device": "dense.paper", "host": "host.paper", "service": "service.pca_poisson",
            "burst": "service.pca_poisson"}
ADDED = {"burst": ("solves_per_s",)}


def make_checkout(tmp: Path, kind: str, limits=None, config=None, chips: int = 1,
                  files=None, **traffic) -> Path:
    """A copy of the benchmark plus one new cell, `tiny.<kind>`, added as
    data files and BENCHMARK.json entries only: `config` overrides fields
    of its configuration, `traffic` of its traffic mix, `files` adds files
    under the copy's bench/ (path: text)."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in (files or {}):
        assert not (root / "bench" / name).exists(), f"{name} would be an edit"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = f"tiny.{kind}"
    mix = dict(TINY_TRAFFIC[kind], **traffic)
    bench["configs"].append({"name": TINY_CONFIG["name"], "source": "rehearsal",
                             "file": "bench/configs/tiny_2048x256.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": cell, "config": TINY_CONFIG["name"],
                               "traffic": mix["name"], "chips": chips, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if TEMPLATE[kind] in m.get("workloads", ()) or m["name"] in ADDED.get(kind, ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny_2048x256.json").write_text(
        json.dumps(dict(TINY_CONFIG, **(config or {}))))
    (root / f"bench/traffic/{mix['name']}.json").write_text(json.dumps(mix))
    (root / f"bench/limits/{cell}.json").write_text(json.dumps(limits or LIMITS))
    for name, text in (files or {}).items():
        (root / "bench" / name).write_text(text)
    return root


def run_cell(root: Path, workload: str, trace: int = 0, seed: int = 2**31 + 7,
             patch: str = "", seconds: float = 1.0, devices: int = 1) -> dict:
    """Run the harness in a child process on the CPU (with `devices` host
    devices), with `patch` executed first; return its result."""
    code = (
        "import sys; sys.path.insert(0, %r); sys.argv[0] = 'run.py'\n"
        "import run\n%s\n"
        "sys.exit(run.main(['--workload', %r, '--seed', '%d', "
        "'--seconds', '%r', '--trace', '%d'], root=__import__('pathlib').Path(%r), "
        "require_tpu=False))\n"
    ) % (str(BENCH), patch, workload, seed, seconds, trace, str(root))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    last = proc.stdout.strip().splitlines()[-1]
    result = json.loads(last)
    assert list(result)[-1] == "check"
    tail = proc.stderr.strip().splitlines()[-len(result["check"]):]
    assert all(line.startswith("check ") for line in tail), tail
    return result


@pytest.mark.parametrize("kind", ["device", "host", "service", "burst"])
def test_new_cell_runs_from_data_alone(tmp_path, kind):
    root = make_checkout(tmp_path, kind)
    result = run_cell(root, f"tiny.{kind}")
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 1
    reported = {"service": {"latency_ms_p95"},
                "burst": {"latency_ms_p95", "solves_per_s"}}.get(kind, {"solves_per_s"})
    assert set(result["metrics"]) == reported | {"setup_s"}
    assert result["device"]["count"] == 1
    checked = result["check"]
    assert set(checked) == set(LIMITS)
    assert checked["residual_ratio"]["value"] < 1.1
    assert checked["repeat_identical"]["value"] == 0


@pytest.mark.parametrize("form", sorted(FORMS))
def test_new_configuration_form_runs_from_data_alone(tmp_path, form):
    config, limits = FORMS[form]
    root = make_checkout(tmp_path, "device", limits=limits, config=config)
    result = run_cell(root, "tiny.device")
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["check"]) == set(limits)
    assert result["check"]["repeat_identical"]["value"] == 0


#: wraps the program's entry: every call must pass a ShardedOp whose array
#: lies row-sharded over the cell's 4 devices
SPANS_FOUR = """
from repro import linalg
_real = linalg.decompose
def spanning(a, spec, **kw):
    m, n = a.shape
    ok = (isinstance(a, linalg.ShardedOp) and len(a.array.sharding.device_set) == 4
          and a.array.sharding.shard_shape((m, n)) == (m // 4, n))
    if not ok:
        raise AssertionError(f"not row-sharded over 4 devices: {a!r}")
    return _real(a, spec, **kw)
linalg.decompose = spanning
"""


def test_row_sharded_cell_spans_its_chips(tmp_path):
    """`placement: rows` on a 4-chip cell, on 4 host devices: every member
    is row-sharded over all four and reaches the program as a ShardedOp,
    and the judge walks it on each device's own rows."""
    root = make_checkout(tmp_path, "device", config={"placement": "rows"}, chips=4)
    result = run_cell(root, "tiny.device", patch=SPANS_FOUR, devices=4)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["count"] == 4


def test_cell_on_four_chips_without_placement_is_refused_before_setup(tmp_path):
    import run

    root = make_checkout(tmp_path, "device", chips=4)
    with pytest.raises(ValueError, match="names no placement"):
        run.load_cell(root, "tiny.device")


#: a kind of its own, found by the configuration's "kind": a dense matrix
#: made by numpy on the host, sigma_i = 0.9^i, judged in float64 numpy
NUMPY_KIND = '''"""Dense A = Q1 diag(0.9^i) Q2^T from numpy's generator, judged in numpy."""
import jax
import numpy as np


class Pool:
    def __init__(self, host, sigma, device):
        self.host, self.sigma = host, sigma
        self.members = [jax.device_put(A.astype(np.float32), device) for A in host]


def build_pool(cfg, traffic, seed, devices):
    rng = np.random.default_rng(seed)
    m, n = cfg["m"], cfg["n"]
    sigma = 0.9 ** np.arange(n)
    Q1 = np.linalg.qr(rng.standard_normal((m, n)))[0]
    Q2 = np.linalg.qr(rng.standard_normal((n, n)))[0]
    base = (Q1 * sigma) @ Q2.T
    host = [base[rng.permutation(m)] for _ in range(traffic["pool"])]
    return Pool(host, sigma, devices[0])


def judge(pool, member, factors, cfg):
    k = cfg["rank"]
    U, S, Vt = (np.asarray(x, np.float64) for x in factors)
    A = pool.host[member]
    optimal = np.sqrt((pool.sigma[k:] ** 2).sum() / (pool.sigma ** 2).sum())
    residual = np.linalg.norm(A - (U * S) @ Vt) / np.linalg.norm(A)
    return {"residual_ratio": float(residual / optimal),
            "sigma_rel_err_top1": float(abs(S[0] - pool.sigma[0]) / pool.sigma[0])}


def required_work(cfg):
    m, n, s, q = cfg["m"], cfg["n"], cfg["rank"] + cfg["oversample"], cfg["power_iters"]
    return {"flops": (2 * q + 2) * 2 * m * n * s, "bytes": (q + 1) * m * n * 4}
'''


def test_kind_of_its_own_found_by_name(tmp_path):
    limits = {"residual_ratio": 1.1, "sigma_rel_err_top1": 1e-4, "repeat_identical": 0}
    root = make_checkout(tmp_path, "device", limits=limits, config={"kind": "numpy_dense"},
                         files={"kinds/numpy_dense.py": NUMPY_KIND})
    result = run_cell(root, "tiny.device")
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["check"]) == set(limits)


def test_kinds_import_nothing_of_the_program():
    for path in (BENCH / "kinds").glob("*.py"):
        code = [line for line in path.read_text().splitlines()
                if line.lstrip().startswith(("import ", "from "))]
        assert not [line for line in code if "repro" in line], path


def test_per_call_sketch_seeds_fit_a_signed_32_bit_int():
    import loadgen

    traffic = {"pool": 17}
    run_seed = 2 ** 32 + 2 ** 31 - 2
    calls = [loadgen.call(traffic, run_seed, i) for i in range(4)]
    assert [seed for _, seed in calls] == [2 ** 31 - 2, 2 ** 31 - 1, 0, 1]
    assert [member for member, _ in calls] == [0, 1, 2, 3]


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense.paper", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and bench/: the program is missing."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense.paper", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
