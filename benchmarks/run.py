"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --only fig4

Writes results/bench.json and prints a summary with the per-claim
reproduction verdicts.
"""

from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import (
    bench_batchfuse,
    bench_chaos,
    bench_dma_gather,
    bench_earlystop_fused,
    bench_fig1_runtime,
    bench_fig2_stability,
    bench_fig3_earlystop,
    bench_fig4_pruning,
    bench_fig5_memory,
    bench_multi_interest,
    bench_serving,
    bench_sharded,
    bench_smoke,
    bench_table1_hitrate,
    bench_table3_bias,
    bench_traffic,
    bench_two_stage,
    bench_widepack,
)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "table1": ("Table 1: hit-rate vs content baselines",
               bench_table1_hitrate.run),
    "table3": ("Table 3: biased-walk language lift", bench_table3_bias.run),
    "fig1": ("Fig 1: runtime vs steps / query size", bench_fig1_runtime.run),
    "fig2": ("Fig 2: stability vs steps", bench_fig2_stability.run),
    "fig3": ("Fig 3: early stopping", bench_fig3_earlystop.run),
    "fig4": ("Fig 4: pruning link-prediction F1", bench_fig4_pruning.run),
    "fig5": ("Fig 5: memory/runtime vs pruning", bench_fig5_memory.run),
    "serving": ("Serving fleet QPS/latency (§3.3)", bench_serving.run),
    "smoke": ("Serving smoke: xla vs pallas walk engines -> "
              "BENCH_serving.json", bench_smoke.run),
    "earlystop_fused": ("Fused in-VMEM early-stop tally vs full re-histogram",
                        bench_earlystop_fused.run),
    "widepack": ("Wide (slot, pin) lanes: id spaces past 2**31 + "
                 "incremental event checks", bench_widepack.run),
    "dma_gather": ("Double-buffered async-DMA CSR prefetch vs scalar "
                   "gathers", bench_dma_gather.run),
    "batchfuse": ("Batch-native fused walk engine: one Pallas program per "
                  "chunk for the whole query batch", bench_batchfuse.run),
    "sharded": ("Pod-sharded batched fused walk engine: per-shard "
                "supersteps on the bounded routing fabric",
                bench_sharded.run),
    "traffic": ("Continuous-traffic serving: bucketed deadline-aware "
                "batches under an open-loop Poisson load generator",
                bench_traffic.run),
    "two_stage": ("Fused two-stage retrieval -> ranking: batched walk + "
                  "embedding-bag neighborhoods + scenario heads",
                  bench_two_stage.run),
    "multi_interest": ("Multi-interest users: clustered queries as budgeted "
                       "lanes on the batch axis + Eq. 3 cross-cluster merge",
                       bench_multi_interest.run),
    "chaos": ("Degraded-mode serving: elastic shed budgets, dead-shard "
              "tolerance, seeded fault injection", bench_chaos.run),
}

VERDICT_KEYS = (
    "ordering_reproduced", "bias_lift_reproduced", "near_linear",
    "query_size_sublinear", "stability_grows_with_steps",
    "early_stop_saves_steps", "edges_monotone_in_delta",
    "pruning_improves_f1", "memory_decreases", "batching_overhead_bounded",
    "both_backends_agree", "fused_matches_naive", "earlystop_backends_agree",
    "widepack_backends_agree", "incremental_matches_full",
    "dma_backends_agree", "batch_engine_agrees", "sharded_engine_agrees",
    "traffic_buckets_agree", "two_stage_backends_agree",
    "multi_interest_agrees", "degraded_serving_agrees",
)


def _flatten(d, prefix=""):
    for k, v in d.items():
        if isinstance(v, dict):
            yield from _flatten(v, prefix + k + ".")
        else:
            yield k, v


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--out", default="results/bench.json")
    ap.add_argument("--in-process", action="store_true",
                    help="run suites in this process (default: one "
                    "subprocess per suite — XLA CPU JIT memory accumulates "
                    "across suites otherwise)")
    args = ap.parse_args(argv)
    # sets a config value only: the parent still starts no backend
    enable_compile_cache()

    names = args.only or list(SUITES)

    if not args.in_process and len(names) > 1:
        import subprocess
        import sys

        results = {}
        os.makedirs("results/bench_parts", exist_ok=True)
        rc_all = 0
        for name in names:
            part = f"results/bench_parts/{name}.json"
            rc = subprocess.run(
                [sys.executable, "-m", "benchmarks.run", "--in-process",
                 "--only", name, "--out", part],
            ).returncode
            rc_all |= rc
            try:
                with open(part) as f:
                    results.update(json.load(f))
            except Exception as e:
                results[name] = {"error": f"subprocess failed: {e}"}
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        n_claims = n_ok = 0
        for res in results.values():
            for k, v in _flatten(res):
                if k in VERDICT_KEYS:
                    n_claims += 1
                    n_ok += bool(v)
        print(f"\nwrote {args.out}")
        print(f"paper-claim verdicts: {n_ok}/{n_claims} reproduced")
        return 0 if (n_ok == n_claims and not rc_all) else 1

    results = {}
    n_errors = 0
    for name in names:
        title, fn = SUITES[name]
        t0 = time.time()
        print(f"== {title} ==", flush=True)
        try:
            res = fn()
            res["_seconds"] = round(time.time() - t0, 1)
            results[name] = res
            verdicts = {
                k: v for k, v in _flatten(res) if k in VERDICT_KEYS
            }
            print(json.dumps(verdicts), f"({res['_seconds']}s)", flush=True)
        except Exception as e:  # record, keep going
            n_errors += 1
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            print("FAILED:", results[name]["error"], flush=True)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nwrote {args.out}")

    n_claims = n_ok = 0
    for res in results.values():
        for k, v in _flatten(res):
            if k in VERDICT_KEYS:
                n_claims += 1
                n_ok += bool(v)
    print(f"paper-claim verdicts: {n_ok}/{n_claims} reproduced"
          + (f" ({n_errors} suite(s) crashed)" if n_errors else ""))
    # a crashed suite contributes no verdicts — it must not look like a pass
    return 0 if (n_ok == n_claims and n_errors == 0) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
