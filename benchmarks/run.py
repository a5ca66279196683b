"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only substring] [--json PATH]

Prints ``name,us_per_call,derived`` CSV (one row per curve point / cell) and
writes a machine-readable ``BENCH_kernels.json`` (row name -> us_per_call,
plus the derived string) so the perf trajectory is tracked across PRs.
Paper mapping:
  bench_qoi_error            Figs 4/5/6   estimated vs actual QoI errors
  bench_rate_distortion      Figs 2/7/8   bitrate vs requested error, 3 methods
  bench_basis                Fig 3        PMGARD-OB vs -HB estimate gap
  bench_refactor_time        Table IV     refactor + retrieval times
  bench_transfer             Fig 9        modelled remote transfer, 2.02x claim
                                          + real store/WAN prefetch overlap
  bench_store                (impl)       container round-trip, fetch latency,
                                          prefetch hit rate, crc32c
  bench_entropy              (impl)       plane-codec density sweep + cost-
                                          model selection vs zlib stand-in
  bench_robustness           (impl)       retrieval under injected transient
                                          faults: wall time + wire bytes at
                                          0/1/5% per-read fault rates
  bench_memory_bound         (impl)       contribution-cache budgets: peak
                                          bytes + warm latency at 1/.5/.25x
  bench_serve_concurrent     (impl)       serve plane: 64 clients, worker
                                          pool + coalescing vs sequential
                                          (speedup, p50/p99 tail amp)
  bench_kernels              (impl)       kernel hot-loop micro-benches
  bench_training_integration (beyond)     progressive ckpt + grad compression
Roofline/dry-run tables are built by benchmarks/roofline.py from
results/dryrun.json (see EXPERIMENTS.md §Roofline).
"""
import argparse
import json
import sys
import time

MODULES = [
    "bench_qoi_error",
    "bench_rate_distortion",
    "bench_basis",
    "bench_refactor_time",
    "bench_transfer",
    "bench_store",
    "bench_entropy",
    "bench_robustness",
    "bench_memory_bound",
    "bench_serve_concurrent",
    "bench_kernels",
    "bench_training_integration",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="run only modules whose name contains one of "
                         "these comma-separated substrings")
    ap.add_argument("--json", default=None,
                    help="machine-readable output path ('' to disable); "
                         "defaults to BENCH_kernels.json on FULL runs only "
                         "— a --only run would clobber it with partial rows")
    args = ap.parse_args()
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    if args.json is None:
        args.json = "" if args.only else "BENCH_kernels.json"
    print("name,us_per_call,derived")
    failures = 0
    results = {}
    only = [s for s in args.only.split(",") if s]
    for name in MODULES:
        if only and not any(s in name for s in only):
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            continue
        for row in rows:
            nm, us, derived = row
            print(f"{nm},{us:.1f},{derived}", flush=True)
            results[nm] = {"us_per_call": round(us, 1), "derived": derived}
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.json and results and not failures:
        # never clobber the cross-PR tracking file with a partial row set
        with open(args.json, "w") as fh:
            json.dump(results, fh, indent=1, sort_keys=True)
        print(f"# wrote {args.json} ({len(results)} rows)", flush=True)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
