# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
#
#   PYTHONPATH=src python -m benchmarks.run [--only fig8,fig10] [--quick]
#
# Sections:
#   bench_graph    — paper Figs 5/7/8/9/10/11, Tables III/V + scheduler
#   bench_cluster  — multi-process cluster runtime: comm-mode wire bytes
#                    sweep + N-server scaling (JSON artifact)
#   bench_serve_graph — online graph-query serving: p50/p99 latency +
#                    queries/sec vs q_slots and offered QPS (JSON artifact)
#   bench_serve_http — the stdlib HTTP frontend over a real socket:
#                    client-observed p50/p99 vs offered QPS + the DRR
#                    fairness ratio under 10:1 tenant skew (JSON artifact)
#   bench_kernels  — Pallas kernel + GAB superstep throughput
#   bench_train    — LM train-step throughput (CPU, reduced configs)
import argparse
import sys
import time
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on bench names")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes (CI: exercise the code paths, "
                         "not the numbers)")
    args = ap.parse_args()

    from benchmarks import (bench_cluster, bench_graph, bench_kernels,
                            bench_serve_graph, bench_serve_http,
                            bench_train, common)

    common.SMOKE = args.smoke

    fns = (bench_graph.ALL + bench_cluster.ALL + bench_serve_graph.ALL
           + bench_serve_http.ALL + bench_kernels.ALL + bench_train.ALL)
    if args.only:
        keys = args.only.split(",")
        fns = [f for f in fns if any(k in f.__name__ for k in keys)]
    print("name,us_per_call,derived")
    failures = 0
    for fn in fns:
        t0 = time.time()
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{fn.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()
        print(f"# {fn.__name__} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
