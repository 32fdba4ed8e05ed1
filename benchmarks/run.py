"""Benchmark harness entry point — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick suite
  PYTHONPATH=src python -m benchmarks.run --full     # everything
  REPRO_BENCH_ROUTERS=knn10,knn100-ivf,linear ... --only table2

Router subsets are spec strings (`repro.core.routers.spec` grammar, e.g.
``knn100-ivf@nprobe=16``) and are passed to each table explicitly — quick
mode never mutates the environment, so ``--only table2`` after a quick run
still sees the full default router set.

Prints a ``name,us_per_call,derived`` CSV summary line per benchmark and
writes per-table CSVs under results/.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="run every table at the full router set")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. table2,fig1")
    ap.add_argument("--emit-bench", default=None, metavar="PATH",
                    help="write a machine-readable retrieval perf snapshot "
                         "(p50 route latency / recall@k / index bytes per "
                         "backend) to PATH, e.g. BENCH_retrieval.json; "
                         "implies running the 'ivf' sweep")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bandit_online, fault_recovery, fig1_locality,
                   gateway_load, intrinsic_dim, ivf_recall, seed_stability,
                   serving_latency, table2_text_auc, table3_latency,
                   table4_ood, table5_vlm_auc, tableD_selection,
                   tableF_scaling, tableI_embeddings,
                   thm72_sample_complexity)

    # quick mode exercises the harness end-to-end on the fast tables; the
    # complete 12-router Tables 2/4/5/D/I ship in results/ from `--full`.
    quick_default = ["fig1", "intrinsic", "tableF", "seeds", "table3"]
    full_suite = quick_default + ["table4", "table5", "tableD", "tableI",
                                  "seeds", "bandit", "ivf", "serving",
                                  "faults", "gateway"]
    jobs = {
        "ivf": ivf_recall.run,
        "serving": serving_latency.run,
        "faults": fault_recovery.run,
        "gateway": gateway_load.run,
        "table2": table2_text_auc.run,
        "table3": table3_latency.run,
        "table4": table4_ood.run,
        "table5": table5_vlm_auc.run,
        "tableD": tableD_selection.run,
        "tableF": tableF_scaling.run,
        "tableI": tableI_embeddings.run,
        "fig1": fig1_locality.run,
        "intrinsic": intrinsic_dim.run,
        "thm72": thm72_sample_complexity.run,
        "seeds": seed_stability.run,
        "bandit": bandit_online.run,
    }
    selected = (args.only.split(",") if args.only
                else (full_suite if args.full else quick_default))
    if args.emit_bench:
        # the retrieval snapshot rides on the ivf sweep; bind the emit path
        # into its job entry so the selection loop below needs no special case
        jobs["ivf"] = functools.partial(ivf_recall.run, emit=args.emit_bench)
        if "ivf" not in selected:
            selected = selected + ["ivf"]
    # quick mode: the simple-method subset, passed EXPLICITLY to the router
    # tables (full 12-router sweep via --full; its CSVs ship under results/)
    quick_routers = None
    if not args.full and not os.environ.get("REPRO_BENCH_ROUTERS"):
        quick_routers = ["knn10", "knn100", "knn10-ivf", "knn100-ivf",
                         "knn100-ivfpq", "linear", "linear_mf", "mlp",
                         "mlp_mf"]
    router_jobs = {"table2", "table3", "table4", "table5", "tableD", "tableI"}

    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        kw = ({"routers": quick_routers}
              if quick_routers and name in router_jobs else {})
        try:
            rows = jobs[name](**kw)
            dt = time.time() - t0
            n = max(len(rows), 1) if rows is not None else 1
            print(f"{name},{dt / n * 1e6:.0f},rows={n} wall={dt:.1f}s")
        except Exception as e:  # noqa: BLE001
            print(f"{name},-1,FAILED:{type(e).__name__}:{e}")
            import traceback
            traceback.print_exc()
    sys.stdout.flush()


if __name__ == "__main__":
    main()
