# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  bench_spectra   -> Figs 2-4 (fast/sharp/slow-decay k-SV speed vs baselines)
  bench_pca       -> Fig 1    (PCA at increasing image resolution)
  bench_sumc      -> Table 1  (SuMC subspace clustering, solver swap)
  bench_kernels   -> kernel microbenches + fused-sketch HBM-traffic model
  bench_rsvd      -> rSVD variants + fused-power traffic model -> BENCH_rsvd.json
"""
import pathlib
import sys
import traceback

# Make `benchmarks` importable when invoked as `python benchmarks/run.py`
# (script dir, not the repo root, lands on sys.path by default).
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> None:
    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import bench_kernels, bench_pca, bench_rsvd, bench_spectra
    from benchmarks import bench_sumc

    modules = [
        ("spectra", bench_spectra),
        ("pca", bench_pca),
        ("sumc", bench_sumc),
        ("kernels", bench_kernels),
        ("rsvd", bench_rsvd),
    ]
    failures = 0
    for name, mod in modules:
        print(f"# --- {name} ---", flush=True)
        try:
            mod.main()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},0,FAILED")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
