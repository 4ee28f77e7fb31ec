"""Readings for setting the benchmark's limits, many seeds in one process
(the programs compile once). Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 10 [--control] [--trace] [--set check_steps=40] \\
        [--sweep rate_per_s=6,8,10] [--out readings.jsonl] \\
        [--dump-trace path.json]

Per seed it prints one JSON line: the numbers compared and their
distribution (the served tokens' gaps and, with --control, the gaps of
the tokens the fp8 control puts first), the cell's end-to-end metrics,
with --trace its per-layer metrics, the compiles inside the window, the
largest group the scheduler prefilled per prompt length, and the queue
left at the close. ``--set`` overrides a key of the traffic mix;
``--sweep`` runs every seed at each value of one key in turn (the knee
sweep varies the rate this way). Needs the TPU, as a run does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import run as bench_run


def readings(run, e2e, per_layer, traced, meter):
    rep = run.replay
    g, c = rep["gaps"], rep["control_gaps"]
    groups, in_window = {}, {}
    for ev in run.events:
        if ev[0] == "prefill":
            g_, t_ = ev[1].shape
            groups[t_] = max(groups.get(t_, 0), g_)
            if ev[2] >= run.t_open:
                k = f"{g_}x{t_}"
                in_window[k] = in_window.get(k, 0) + 1
    out = {"correct": run.correct, "compared": run.compared,
           "tokens_checked": int(len(g)),
           "gap_max": float(g.max()) if len(g) else None,
           "gap_p99": float(np.percentile(g, 99)) if len(g) else None,
           "gap_nonzero_share": float((g > 0).mean()) if len(g) else None,
           "control_gap_max": float(c.max()) if len(c) else None,
           "control_gap_nonzero_share": float((c > 0).mean())
           if len(c) else None,
           "phases": phases(run, meter),
           "reference_s": run.reference_s,
           "after_window_s": run.t_end - run.t_close,
           "attempted": run.attempted, "failed": run.failed,
           "peak_bytes": run.peak_bytes,
           "compiles_in_window": [e[:3] for e in run.compile_events],
           "max_group": groups,
           "groups_in_window": in_window,
           "wait_by_third_s": wait_by_third(run),
           "unanswered_at_close": sum(
               1 for cl in run.clients
               if cl.sent < run.t_close and not cl.first < run.t_close),
           "metrics": {}}
    for m in e2e + (per_layer if traced else []):
        try:
            v = bench_run.load_module("metrics", m["name"]).read(run)
        except RuntimeError as e:     # a reading's own check; keep the rest
            v = f"error: {e}"
        out["metrics"][m["name"]] = v
    if traced:
        out["trace_stats"] = run.trace_stats
    return out


def phases(run, meter):
    """[name, seconds, compiles and cache loads in it] per set-up phase."""
    out = []
    for (_, a), (name, b) in zip(run.marks, run.marks[1:]):
        out.append([name, b - a, len(meter.between(a, b))])
    return out


def wait_by_third(run):
    """Mean wait from due to first token of the requests due in each
    third of the window: a backlog that grows shows as a rising wait."""
    thirds = [[], [], []]
    for c in run.clients:
        if run.t_open <= c.due < run.t_close and c.first == c.first:
            k = min(2, int(3 * (c.due - run.t_open) / run.seconds))
            thirds[k].append(c.first - c.due)
    return [float(np.mean(t)) if t else None for t in thirds]


def dump_slice(trace, path, length_ns=800e6):
    """The first `length_ns` of the traced slice, as trace.load gives
    it, with the window span cut to that length (a small recorded trace
    for tests/test_trace.py)."""
    import tracing as tr
    lo, _ = tr.window(trace)
    hi = lo + length_ns
    small = {"devices": {p: [op for op in ops if lo <= op[1] < hi]
                         for p, ops in trace["devices"].items()},
             "operands": trace.get("operands", {}),
             "spans": [list(s) for s in trace["spans"]
                       if lo <= s[1] < hi and s[0] != tr.WINDOW_SPAN]
             + [[tr.WINDOW_SPAN, lo, hi, -1]]}
    with open(path, "w") as f:
        json.dump(small, f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--sweep")
    ap.add_argument("--out")
    ap.add_argument("--dump-trace")
    args = ap.parse_args()
    _, cell, cfg, e2e, per_layer = bench_run.load_cell(args.workload)
    bench_run.enable_cache()
    devs = bench_run.require_chips(cell["chips"])
    meter = bench_run.CompileMeter()
    import runner
    import traffic
    from peaks import peaks
    mix = traffic.load_mix(cell["traffic"])
    for kv in args.set:
        k, v = kv.split("=", 1)
        mix[k] = json.loads(v)
    lim = runner.limits(cell["name"])
    points = [(None, None)]
    if args.sweep:
        key, vals = args.sweep.split("=", 1)
        points = [(key, json.loads(v)) for v in vals.split(",")]
    for (key, val), seed in [(p, int(s)) for p in points
                             for s in args.seeds.split(",")]:
        if key is not None:
            mix[key] = val
        t0 = time.perf_counter()
        run = runner.run_cell(cell, cfg, mix, lim, seed=seed,
                              seconds=args.seconds, traced=args.trace,
                              meter=meter, t_process=t0, devices=devs,
                              device_peaks=peaks(devs[0].device_kind),
                              control=args.control)
        if args.dump_trace and run.trace is not None:
            dump_slice(run.trace, args.dump_trace)
            args.dump_trace = None
        r = readings(run, e2e, per_layer, args.trace, meter)
        r.update(seed=seed, overrides=args.set + (
            [f"{key}={val}"] if key is not None else []),
                 wall_s=time.perf_counter() - t0)
        line = json.dumps(r, default=float)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
