"""Where a decode step's device time goes, scope by scope, and what the
scheduler was doing while the device waited, from one traced run.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--dump path.json]

runs one traced run of a cell as ``bench/run.py --trace 1`` does and
prints one JSON line: the cell's metrics, the device ms per decode step
of every scope of ``tracing.SCOPES`` with the ops left to "other", the
device's idle time split among the program's ``serve/`` spans, and those
spans' counts, times and summed counters. The benchmark's own per-layer
metrics read a few of these; this is the rest, for finding the next
bottleneck. ``--dump`` writes the slice's first decode round as a test
fixture (tests/test_scopes.py).
"""
from __future__ import annotations

import argparse
import collections
import json
import re
from typing import Dict, Optional

import tracing as tr


def decode_breakdown(run) -> Optional[Dict]:
    """Per-step scope ms, busy ms and the share of "other" over the
    harness's fused decode calls in the traced slice."""
    import reading
    per = reading.decode_scope_ms(run)
    if per is None:
        return None
    spans = reading.traced_calls(run, "fused_decode")
    steps = sum(run.events[c][2][1].shape[0] for _, _, c in spans)
    busy = tr.busy_first_ns(run.trace, spans) / 1e6 / steps
    ops = collections.defaultdict(float)
    unnamed = 0.0      # ops the compiled text does not name at all
    leafy = tr.leaves(tr.first_plane(run.trace))
    for a, b, _ in spans:
        for name, s, e in leafy:
            if s >= a and e <= b and tr.scope_of(run.trace, name) == tr.OTHER:
                ops[re.sub(r"\.\d+$", "", name)] += (e - s) / 1e6 / steps
                if name not in run.trace["scopes"]:
                    unnamed += (e - s) / 1e6 / steps
    return {"calls": len(spans), "steps": steps, "scope_ms": per,
            "busy_ms": busy, "charged_ms": sum(per.values()),
            "other_share": per.get(tr.OTHER, 0.0) / busy if busy else None,
            "other_ops_ms": sorted(ops.items(), key=lambda kv: -kv[1])[:8],
            "unnamed_ms": unnamed}


def idle_by_span(trace: Dict) -> Dict[str, float]:
    """{program span: device idle ns while it was the innermost serve/
    span}, over the window; idle time under no serve/ span is "none".
    Unlike a gap's name, this splits each gap among the host phases it
    spans."""
    serve = [(s, e) for n, s, e, _ in trace["spans"]
             if n.startswith(tr.SERVE)]
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in tr.idle_intervals(trace):
        cuts = sorted({a, b} | {t for s, e in serve for t in (s, e)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            out[tr.innermost_serve_span(trace, (x + y) / 2) or "none"] += \
                y - x
    return dict(out)


def span_totals(trace: Dict) -> Dict[str, Dict[str, float]]:
    """{serve/ span: its count, total ms and the sum of each numeric
    counter} over the program spans that start inside the window."""
    lo, hi = tr.window(trace)
    ends = {(n, s): e for n, s, e, _ in trace["spans"]}
    out: Dict[str, Dict[str, float]] = {}
    for name, start, args in trace["serve_args"]:
        if lo <= start <= hi:
            t = out.setdefault(name, collections.defaultdict(float))
            t["count"] += 1
            t["ms"] += (ends[(name, start)] - start) / 1e6
            for k, v in args.items():
                if isinstance(v, (int, float)) and k not in ("round", "rid"):
                    t[k] += v
    return {k: dict(v) for k, v in out.items()}


def dump(trace: Dict, path: str):
    """The slice from the start of the first program decode round in
    the window to the start of the next, each widened by 1 ms, as
    ``tracing.load`` gives it, with the window cut to that length: a
    small recorded trace for tests/test_scopes.py."""
    decode = [s for s, _, c in tr.spans_named(trace, "serve/decode")
              if c == -1]
    lo, hi = decode[0] - 1e6, decode[1] + 1e6
    devices = {p: [list(op) for op in ops if lo <= op[1] and op[2] <= hi]
               for p, ops in trace["devices"].items()}
    names = {op[0] for ops in devices.values() for op in ops}
    small = {"devices": devices,
             "operands": {k: v for k, v in trace["operands"].items()
                          if k in names},
             "scopes": {k: v for k, v in trace["scopes"].items()
                        if k in names},
             "fused": {k: [o for o in v if tr.deepest_scope(o)]
                       for k, v in trace["fused"].items() if k in names},
             "spans": [list(s) for s in trace["spans"]
                       if lo <= s[1] <= hi and s[0] != tr.WINDOW_SPAN]
             + [[tr.WINDOW_SPAN, lo, hi, -1]]}
    with open(path, "w") as f:
        json.dump(small, f)


def main():
    import calibrate
    import run as bench_run
    import runner
    import traffic
    from peaks import peaks

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump")
    args = ap.parse_args()
    _, cell, cfg, e2e, per_layer = bench_run.load_cell(args.workload)
    bench_run.enable_cache()
    devs = bench_run.require_chips(cell["chips"])
    meter = bench_run.CompileMeter()
    run = runner.run_cell(cell, cfg, traffic.load_mix(cell["traffic"]),
                          runner.limits(cell["name"]), seed=args.seed,
                          seconds=args.seconds, traced=True, meter=meter,
                          t_process=bench_run.T_PROCESS, devices=devs,
                          device_peaks=peaks(devs[0].device_kind))
    out = calibrate.readings(run, e2e, per_layer, True, meter)
    out.update(seed=args.seed, decode=decode_breakdown(run),
               idle_by_span=idle_by_span(run.trace),
               serve_spans=span_totals(run.trace))
    if args.dump:
        dump(run.trace, args.dump)
    print(json.dumps(out, default=float), flush=True)


if __name__ == "__main__":
    main()
