# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import sys
import traceback

MODULES = [
    "benchmarks.bench_upload",      # Fig 4(a,b,c)
    "benchmarks.bench_scale",       # Table 2 + Fig 5
    "benchmarks.bench_query",       # Fig 6 + Fig 7
    "benchmarks.bench_failover",    # Fig 8 (+ straggler mitigation)
    "benchmarks.bench_splitting",   # Fig 9
    "benchmarks.bench_adaptive",    # LIAH convergence (lazy -> indexed)
    "benchmarks.bench_governor",    # budget eviction + workload-shift reconvergence
    "benchmarks.bench_server",      # shared-scan serving + hot-block cache
    "benchmarks.bench_kernels",     # Pallas kernel harness
    "benchmarks.bench_roofline",    # roofline table from the dry-run
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module name")
    args = ap.parse_args()
    from repro.compile_cache import place_compile_cache
    place_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:
            failed += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{mod_name},nan,ERROR", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
