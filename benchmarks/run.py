"""Benchmark harness entry point — one module per paper table/figure.

  sort        Fig 2   out-of-core sort page-size sweep
  bfs         Fig 3   BFS on out-of-core CSR graph
  lrzip       Fig 4   rolling-hash compression scan
  asteroid    Fig 5/6 image-cube vector tracing, local vs remote store
  nstore      Fig 7/8 YCSB KV transactions + executor scaling
  fault_storm §3.3    multi-threaded fault storm: shard-count scaling,
              steal/contention counters (DESIGN.md §12)
  writeback   §3.5    dirty storm: per-page vs coalesced write-back
              (DESIGN.md §13)
  tiering     §3.4    skewed fault storm: heat-driven migration, tiered
              vs slow-tier-only (DESIGN.md §14)
  chaos       §17     scripted fault injection: throughput under faults,
              circuit-broken failover, time-to-recovery (DESIGN.md §17)
  train_ooc   §18     out-of-core training: paged vs resident step time
              at >=4x state oversubscription (DESIGN.md §18)
  fault_overhead  µs/fault microbenchmark feeding the PageSizeAdvisor

Prints ``name,us_per_call,derived`` CSV and writes JSON rows under
experiments/bench/.  ``--full`` runs the larger datasets; default is the
quick configuration suitable for CI.
"""

from __future__ import annotations

import argparse
import sys
import time


def _fault_overhead_rows():
    import numpy as np

    from repro.core import HostArrayStore, UMapConfig, umap, uunmap

    from .common import Row

    n_pages = 2000
    ps = 4096
    store = HostArrayStore(np.zeros(n_pages * ps, np.uint8))
    cfg = UMapConfig(page_size=ps, buffer_size=n_pages * ps, num_fillers=4,
                     num_evictors=1)
    region = umap(store, config=cfg)
    t0 = time.perf_counter()
    for p in range(n_pages):
        region.read(p * ps, 1)
    dt = time.perf_counter() - t0
    uunmap(region)
    rows = [Row("fault_overhead", "umap", ps, dt,
                {"us_per_fault": dt / n_pages * 1e6})]

    # Coalescing comparison: a multi-page read posts adjacent fills that
    # fillers can (or, with max_batch_pages=1, cannot) drain as one batched
    # store call.  The store-call count is the paper-§3.3 decoupling metric.
    for label, batch in (("batch-off", 1), ("batch-on", 16)):
        st = HostArrayStore(np.zeros(n_pages * ps, np.uint8))
        cfg = UMapConfig(page_size=ps, buffer_size=n_pages * ps,
                         num_fillers=4, num_evictors=1, max_batch_pages=batch)
        region = umap(st, config=cfg)
        t0 = time.perf_counter()
        span = 64 * ps
        for lo in range(0, n_pages * ps, span):
            region.read(lo, min(span, n_pages * ps - lo))
        dt = time.perf_counter() - t0
        stats = region.stats()
        uunmap(region)
        rows.append(Row("fault_overhead", label, ps, dt, {
            "store_reads": st.num_reads,
            "coalesced_fills": stats["coalesced_fills"],
            "coalesced_pages": stats["coalesced_pages"],
        }))
    return rows


SUITES = {
    "sort": ("bench_sort", "Fig 2"),
    "bfs": ("bench_bfs", "Fig 3"),
    "lrzip": ("bench_lrzip", "Fig 4"),
    "asteroid": ("bench_asteroid", "Fig 5/6"),
    "nstore": ("bench_nstore", "Fig 7/8"),
    "fault_storm": ("bench_fault_storm", "§3.3 scaling"),
    "writeback": ("bench_writeback", "§3.5 write-back"),
    "tiering": ("bench_tiering", "§3.4 tiered store"),
    "serve": ("bench_serve", "§16 serving"),
    "chaos": ("bench_chaos", "§17 resilience"),
    "train_ooc": ("bench_train_ooc", "§18 OOC training"),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale datasets")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite subset")
    ap.add_argument("--out", default=None, metavar="DIR",
                    help="write result JSON here instead of the committed "
                         "experiments/bench/ (also: UMAP_BENCH_RESULTS_DIR)")
    args = ap.parse_args(argv)
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    out_dir = args.out

    from .common import print_rows, save_rows, speedup_table

    print("name,us_per_call,derived")
    all_ok = True
    for name, (mod_name, fig) in SUITES.items():
        if only and name not in only:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run(quick=quick)
            save_rows(name, rows, out_dir=out_dir)
            for r in rows:
                us = r.seconds * 1e6
                derived = ";".join(f"{k}={v}" for k, v in r.extra.items())
                print(f"{r.workload}/{r.config}/p{r.page_size},{us:.0f},{derived}")
            tbl = speedup_table([r for r in rows if r.workload == name])
            mmap_s = tbl.get("mmap_seconds")
            if mmap_s and mmap_s == mmap_s:      # present and not NaN
                best = max((v["speedup_vs_mmap"]
                            for k, v in tbl.items() if isinstance(k, int)),
                           default=float("nan"))
                print(f"# {name} ({fig}): best UMap speedup vs mmap = {best:.2f}x",
                      flush=True)
            elif name == "fault_storm":          # scales vs shards=1 instead
                summary = next((r for r in rows if r.config == "summary"), None)
                if summary:
                    print(f"# {name} ({fig}): fill-throughput speedup vs "
                          f"shards=1 = {summary.extra['best_speedup']:.2f}x",
                          flush=True)
            elif name == "writeback":            # batched vs per-page drain
                summary = next((r for r in rows if r.config == "summary"), None)
                if summary:
                    ratio = summary.extra["speedup_batched_vs_per_page"]
                    print(f"# {name} ({fig}): drain-throughput speedup "
                          f"batched vs per-page = {ratio:.2f}x", flush=True)
            elif name == "tiering":              # tiered vs slow-tier-only
                summary = next((r for r in rows if r.config == "summary"), None)
                if summary:
                    ratio = summary.extra["speedup_tiered_vs_slow_only"]
                    print(f"# {name} ({fig}): fill-throughput speedup "
                          f"tiered vs slow-only = {ratio:.2f}x", flush=True)
            elif name == "chaos":                # failover + recovery witness
                summary = next((r for r in rows if r.config == "summary"), None)
                if summary:
                    print(f"# {name} ({fig}): degraded/slow-only ratio = "
                          f"{summary.extra['degraded_ratio']:.2f}, recovery "
                          f"in {summary.extra['recovery_s']:.2f}s, "
                          f"{summary.extra['errors_surfaced']} errors "
                          f"surfaced", flush=True)
            elif name == "train_ooc":            # paged-vs-resident witness
                summary = next((r for r in rows if r.config == "summary"), None)
                if summary:
                    print(f"# {name} ({fig}): paged/resident step-time ratio "
                          f"= {summary.extra['step_time_ratio']:.2f} at "
                          f"{summary.extra['oversubscription']:.1f}x "
                          f"oversubscription, readahead hit rate "
                          f"{summary.extra['readahead_hit_rate']:.2f}",
                          flush=True)
            elif name == "serve":                # sharing + isolation witness
                summary = next((r for r in rows if r.config == "summary"), None)
                if summary:
                    print(f"# {name} ({fig}): prefix sharing saved "
                          f"{summary.extra['shared_savings_pages']} peak pages; "
                          f"gold p99 isolation ratio = "
                          f"{summary.extra['isolation_ratio']:.2f}", flush=True)
        except Exception as e:  # noqa: BLE001
            all_ok = False
            print(f"# {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)

    if only is None or "fault_overhead" in (only or set()):
        rows = _fault_overhead_rows()
        save_rows("fault_overhead", rows, out_dir=out_dir)
        for r in rows:
            derived = ";".join(f"{k}={v if isinstance(v, int) else f'{v:.1f}'}"
                               for k, v in r.extra.items())
            print(f"fault_overhead/{r.config},{r.seconds * 1e6:.0f},{derived}")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
