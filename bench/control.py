#!/usr/bin/env python3
"""The control of `correct`: the plain reference put in the program's place.

    python3 bench/control.py --workload <cell> --seeds <n> ... \
        [--precision bf16] [--seconds 2]

Runs the cell as bench/run.py does, once per seed and all in one process,
with the program's entry (`repro.linalg.decompose`, or for an open loop
`DecompositionService`) replaced by the plain reference at one matmul
precision, judged by the harness's own comparison under the cell's
committed limits.  At "bf16", one bf16 pass, the precision below the
float32 that the configuration states, every run must come out not
correct; at "highest" the reference must pass; "high" is read for the
record.  Each run prints its result line, the compared numbers under
"check".  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))


def plant(workload: str, precision: str, root: Path) -> None:
    """Put the reference at `precision` in place of the program's entry:
    `linalg.decompose` for a closed loop, `DecompositionService` for an
    open one."""
    import reference
    import run
    from repro import linalg
    from repro.serve import decomp

    spec = run.load_cell(root, workload)
    cfg = spec["config"]
    args = (precision, cfg["oversample"], cfg["power_iters"])
    if spec["traffic"]["loop"] == "open":
        decomp.DecompositionService = reference.service(*args)
    else:
        linalg.decompose = reference.decompose(*args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=("highest", "high", "bf16"), default="bf16")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    import run

    plant(args.workload, args.precision, run.ROOT)
    rc = 0
    for seed in args.seeds:
        rc |= run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0"])
    return rc


if __name__ == "__main__":
    sys.exit(main())
