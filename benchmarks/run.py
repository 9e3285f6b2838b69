"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (paper-protocol benchmarks at CPU
scale; see benchmarks/common.py for the scale adaptation note).

``--smoke``: run every module at toy scale with repeat=1 (CI keeps the
bench code executed; the numbers are not comparable to full runs).
"""
import sys
import time


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import common
    from benchmarks.common import Csv
    args = [a for a in sys.argv[1:]]
    if "--smoke" in args:
        args.remove("--smoke")
        common.SMOKE = True
    from benchmarks import (bench_ablation, bench_cbr, bench_cdf,
                            bench_clustering, bench_engine, bench_highdim,
                            bench_hybrid, bench_learned_index,
                            bench_measurement, bench_range_knn,
                            bench_reopt, bench_scalability, bench_serve,
                            bench_transform, bench_vector_index)
    modules = [
        ("table6", bench_clustering),
        ("fig7", bench_measurement),
        ("fig10_11", bench_transform),
        ("fig14", bench_cdf),
        ("fig15", bench_learned_index),
        ("fig16", bench_vector_index),
        ("fig19_20", bench_range_knn),
        ("fig21", bench_cbr),
        ("fig22_23", bench_scalability),
        ("fig24", bench_hybrid),
        ("engine", bench_engine),
        ("serve", bench_serve),
        ("reopt", bench_reopt),
        ("fig25_26", bench_highdim),
        ("fig27", bench_ablation),
    ]
    only = args[0] if args else None
    csv = Csv()
    print("name,us_per_call,derived")
    for name, mod in modules:
        if only and only not in name:
            continue
        t0 = time.time()
        mod.run(csv)
        csv.add(f"_meta/{name}/wall_s", (time.time() - t0) * 1e6, "")
        csv.emit()
        csv.rows.clear()


if __name__ == "__main__":
    main()
