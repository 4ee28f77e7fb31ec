"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,...]

Prints a ``name,us_per_call,derived`` CSV line per benchmark (us_per_call
= mean decode-step wall time where measured, else total bench wall), and
writes the full row data to benchmarks/results.json.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback

BENCHES = ("fig1_activation", "fig3_overlap", "fig4_table3_tradeoff",
           "fig5_table4_spec", "table1_mixed", "table2_ep",
           "bs_ablation", "kernels_bench", "continuous_batching")

DERIVED_KEY = {
    "fig1_activation": ("worst_rel_err", "max |emp-formula|/formula"),
    "fig3_overlap": ("k5_ratio_spec_vs_cross",
                     "consecutive/cross overlap ratio @k=5"),
    "fig4_table3_tradeoff": ("reduction_at_(4,1)",
                             "activated-expert reduction @(m=4,k0=1)"),
    "fig5_table4_spec": ("speedup",
                         "scheduler-spec vs plain tokens/s (OTPS model)"),
    "table1_mixed": ("mixed_gain_best", "OTPS-model gain, mixed batch"),
    "table2_ep": ("ep_measured",
                  "measured EP scoreboard (shard_map, 8-dev mesh)"),
    "bs_ablation": ("reduction_bs4",
                    "activated-expert reduction @BS=4 (App B)"),
    "kernels_bench": ("bytes_at_quarter_activation",
                      "HBM bytes @25% activation vs full"),
    "continuous_batching": ("fused_speedup_bs8",
                            "fused-scan OTPS vs lockstep host loop @bs8"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode (reduced shapes). Without "
                         "--only, runs the dispatch shootout + spec "
                         "scoreboard + EP scoreboard (persists "
                         "BENCH_dispatch.json / BENCH_spec.json / "
                         "BENCH_ep.json); with --only, runs exactly "
                         "the named benches in quick mode")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = BENCHES if not args.only else tuple(args.only.split(","))
    if args.quick and not args.only:
        names = ("kernels_bench", "fig5_table4_spec", "table2_ep")

    results = {}
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        quick_ok = "quick" in inspect.signature(mod.run).parameters
        try:
            out = mod.run(quick=True) if args.quick and quick_ok \
                else mod.run()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},ERROR,{e!r}")
            traceback.print_exc()
            continue
        wall_us = (time.perf_counter() - t0) * 1e6
        us = wall_us
        for row in out.get("rows", []):
            if isinstance(row, dict) and "wall_us_per_step" in row:
                us = row["wall_us_per_step"]
                break
        key, desc = DERIVED_KEY[name]
        derived = out.get(key)
        if isinstance(derived, float):
            derived = round(derived, 4)
        print(f"{name},{us:.1f},{derived}")
        results[name] = {"derived_desc": desc, "derived": derived, **out}

    path = os.path.join(os.path.dirname(__file__), "results.json")
    if (args.only or args.quick) and os.path.exists(path):  # merge partials
        merged = json.load(open(path))
        merged.update(results)
        results = merged
    with open(path, "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# wrote {path}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
