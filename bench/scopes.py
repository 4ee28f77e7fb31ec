"""Where a decode step's device time goes, scope by scope, and what the
serving thread was doing in each idle gap, from a traced run.

The program names its own layers. Named scopes put "attention",
"attention/kv_write", "moe", "moe/route", "moe/dispatch", "moe/experts",
"moe/combine", "lm_head" and "sample" into the op_name metadata of each
device instruction (models/, serving/step.py). The scheduler's host
phases are profiler spans named "serve/..." whose arguments are their
counters (serving/scheduler.py, serving/frontdoor.py). On a TPU v5e the
profiler names a device op by its instruction text without the
metadata, so ``load`` takes each instruction's op_name from the text of
the compiled fused decode program, and the spans from the xplane file,
beside what ``tracing.load`` keeps. The functions after it reduce them
and know nothing of the profiler, so a recorded slice and hand-made
traces check them (tests/test_scopes.py).

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--dump path.json]

runs one traced run of a cell as ``bench/run.py --trace 1`` does and
prints one JSON line: the cell's metrics, the device ms per decode step
of each scope, the idle ms per round, the program spans' counts and
times, and a breakdown that leaves out the ops that enclose others and
names each idle gap by the program's span. The harness keeps neither
the compiled text nor the serve/ spans, and deletes the trace after
loading it, so this script puts ``load`` in its place for the run.
``--dump`` writes the slice's first decode round as a test fixture.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import re
from typing import Dict, List, Optional, Tuple

import tracing as tr

SCOPES = ("attention", "attention/kv_write", "moe", "moe/route",
          "moe/dispatch", "moe/experts", "moe/combine", "lm_head", "sample")
OTHER = "other"
SERVE = "serve/"
_KEEP_LOAD = tr.load      # the harness's loader, before main() wraps it


def hlo_op_names(hlo_text: str) -> Tuple[Dict[str, str],
                                          Dict[str, List[str]]]:
    """From a compiled module's text: {instruction: its op_name} and
    {fusion instruction: the op_names of the instructions fused into
    it}. XLA gives a fusion the metadata of its root alone."""
    own: Dict[str, str] = {}
    comps: Dict[str, List[str]] = {}
    calls: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = comps.setdefault(head.group(1), [])
            continue
        if line.startswith("}"):
            comp = None
            continue
        inst = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        meta = re.search(r'op_name="([^"]*)"', line)
        if inst and meta:
            own[inst.group(1)] = meta.group(1)
            if comp is not None and meta.group(1) not in comp:
                comp.append(meta.group(1))
        f = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
        if inst and f:
            calls[inst.group(1)] = f.group(1)
    return own, {name: comps.get(c, []) for name, c in calls.items()}


def load(path: str, hlo_text: str) -> Dict:
    """``tracing.load`` plus "scopes": {instruction: op_name} and
    "fused": {fusion: op_names fused into it}, both from `hlo_text`,
    the compiled fused decode program; and, in "spans", the program's
    ``serve/`` host spans with call -1, their counters in "serve_args":
    [(name, start_ns, {arg: value})]."""
    from jax.profiler import ProfileData
    trace = _KEEP_LOAD(path)
    spans = list(trace["spans"])
    args = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SERVE):
                        spans.append((ev.name, ev.start_ns, ev.end_ns, -1))
                        args.append((ev.name, ev.start_ns, dict(ev.stats)))
    trace["scopes"], trace["fused"] = hlo_op_names(hlo_text)
    trace["spans"] = sorted(spans, key=lambda s: s[1])
    trace["serve_args"] = sorted(args, key=lambda a: a[1])
    return trace


def deepest_scope(op_name: str) -> Optional[str]:
    """The known scope that ends deepest in an op_name path (the longer
    one where two end at the same component), or None."""
    parts = op_name.split("/")
    best, key = None, (-1, 0)
    for scope in SCOPES:
        sp = scope.split("/")
        for i in range(len(parts) - len(sp) + 1):
            if parts[i:i + len(sp)] == sp and (i + len(sp), len(sp)) > key:
                best, key = scope, (i + len(sp), len(sp))
    return best


def scope_of(trace: Dict, op: str) -> str:
    """The scope a device op is charged to: the deepest known scope of
    its own op_name; for a fusion whose root lies in none, the scope
    most of its fused instructions name (the deeper on a tie); else
    "other"."""
    own = deepest_scope(trace.get("scopes", {}).get(op, ""))
    if own is not None:
        return own
    named = [s for s in map(deepest_scope, trace.get("fused", {}).get(op, []))
             if s is not None]
    if not named:
        return OTHER
    count = collections.Counter(named)
    return max(count, key=lambda s: (count[s], s.count("/"), s))


def leaves(ops: List[Tuple[str, float, float]]) -> List[Tuple[str, float,
                                                               float]]:
    """The ops of one line that enclose no other op on it; a container
    (a while loop, a conditional, a call) holds the ops it runs."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (name, s, e) in enumerate(order):
        j = i + 1
        container = False
        while j < len(order) and order[j][1] < e:
            _, s2, e2 = order[j]
            if e2 <= e and (s2, e2) != (s, e):
                container = True
                break
            j += 1
        if not container:
            out.append((name, s, e))
    return out


def first_plane(trace: Dict):
    return trace["devices"][sorted(trace["devices"])[0]]


def charge(trace: Dict, spans) -> Dict[str, float]:
    """{scope: device ns} of the leaf ops on the first chip inside
    `spans` (start, end, ...), each clipped to its span; ops under no
    known scope are charged to "other"."""
    ops = leaves(first_plane(trace))
    starts = [s for _, s, _ in ops]
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b, *_ in spans:
        i = max(0, bisect.bisect_left(starts, a) - 1)
        for name, s, e in ops[i:bisect.bisect_right(starts, b)]:
            d = min(e, b) - max(s, a)
            if d > 0:
                out[scope_of(trace, name)] += d
    return dict(out)


def busy_first_ns(trace: Dict, spans) -> float:
    """Device busy time on the first chip inside `spans`."""
    busy = [(s, e) for _, s, e in first_plane(trace)]
    return sum(y - x for a, b, *_ in spans for x, y in tr.union(busy, a, b))


def ms_per_step(trace: Dict, spans, steps: int) -> Dict[str, float]:
    """{scope: device ms per decode step} over `spans`, which ran
    `steps` decode steps in all."""
    return {k: ns / 1e6 / steps for k, ns in charge(trace, spans).items()}


def moe_ms(per_step: Dict[str, float]) -> float:
    """The MoE layer's ms: "moe" and every scope under it."""
    return sum(v for k, v in per_step.items()
               if k == "moe" or k.startswith("moe/"))


def decode_metrics(trace: Dict, spans, steps: int) -> Dict[str, float]:
    """The per-layer numbers of the fused decode calls `spans`, which
    ran `steps` decode steps: device ms per step of attention (less the
    cache write), of the cache write, of the whole MoE layer and of
    XShare's routing and selection, and the device's idle ms per
    decode round of the program."""
    per = ms_per_step(trace, spans, steps)
    return {"attention_ms.decode": per.get("attention", 0.0),
            "kv_write_ms.decode": per.get("attention/kv_write", 0.0),
            "moe_ms.decode": moe_ms(per),
            "xshare_route_ms.decode": per.get("moe/route", 0.0),
            "idle_ms_per_round.decode": idle_ms_per_round(trace)}


def idle_ms_per_round(trace: Dict, span: str = "serve/decode") -> Optional[
        float]:
    """Device idle time on the first chip between the starts of the
    first and the last program `span` that start inside the window,
    over the rounds that started in between, in ms."""
    lo, hi = tr.window(trace)
    starts = sorted(s for n, s, _, c in trace["spans"]
                    if n == span and c == -1 and lo <= s <= hi)
    if len(starts) < 2:
        return None
    a, b = starts[0], starts[-1]
    return ((b - a) - busy_first_ns(trace, [(a, b)])) / 1e6 / (
        len(starts) - 1)


def innermost_serve_span(trace: Dict, t: float) -> Optional[str]:
    """The program span (``serve/...``) at time `t` that began last,
    i.e. the innermost of those that hold `t`."""
    here = [(s, n) for n, s, e, _ in trace["spans"]
            if n.startswith(SERVE) and s <= t <= e]
    return max(here)[1] if here else None


def idle_intervals(trace: Dict) -> List[Tuple[float, float]]:
    """The stretches of the window with no operation on the first
    chip."""
    lo, hi = tr.window(trace)
    busy = tr.union([(s, e) for _, s, e in first_plane(trace)], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_span(trace: Dict) -> Dict[str, float]:
    """{program span: device idle ns while it was the innermost serve/
    span}, over the window; idle time under no serve/ span is "none".
    Unlike a gap's name, this splits each gap among the host phases it
    spans."""
    serve = [(s, e, n) for n, s, e, _ in trace["spans"]
             if n.startswith(SERVE)]
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in idle_intervals(trace):
        cuts = sorted({a, b} | {t for s, e, _ in serve for t in (s, e)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            out[innermost_serve_span(trace, (x + y) / 2) or "none"] += y - x
    return dict(out)


def idle_gaps(trace: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """``tracing.idle_gaps``, with a gap it would call "harvest" named by
    the innermost program span at its middle."""
    gaps = sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        here = [n for n, s, e, _ in trace["spans"] if s <= mid <= e]
        name = next((n for n in tr.SERVING if n in here),
                    "generator" if "generator" in here else None)
        out.append((name or innermost_serve_span(trace, mid) or "harvest",
                    (b - a) / 1e9))
    return out


def op_seconds(trace: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """``tracing.op_seconds`` over leaf ops only: a container's time is
    that of the ops it holds, which are counted already."""
    leafy = {p: leaves(ops) for p, ops in trace["devices"].items()}
    return tr.op_seconds(dict(trace, devices=leafy), top)


# ------------------------------------------------------------ the run --

def decode_breakdown(run) -> Optional[Dict]:
    """Per-step scope ms, busy ms and the share of "other" over the
    harness's fused decode calls in the traced slice."""
    import reading
    spans = reading.traced_calls(run, "fused_decode")
    if not spans or not run.trace.get("scopes"):
        return None
    steps = sum(run.events[c][2][1].shape[0] for _, _, c in spans)
    per = ms_per_step(run.trace, spans, steps)
    busy = busy_first_ns(run.trace, spans) / 1e6 / steps
    ops = collections.defaultdict(float)
    unnamed = 0.0      # ops the compiled text does not name at all
    leafy = leaves(first_plane(run.trace))
    for a, b, _ in spans:
        for name, s, e in leafy:
            if s >= a and e <= b and scope_of(run.trace, name) == OTHER:
                ops[re.sub(r"\.\d+$", "", name)] += (e - s) / 1e6 / steps
                if name not in run.trace["scopes"]:
                    unnamed += (e - s) / 1e6 / steps
    return {"calls": len(spans), "steps": steps, "scope_ms": per,
            "metrics": decode_metrics(run.trace, spans, steps),
            "busy_ms": busy,
            "charged_ms": sum(per.values()),
            "other_share": per.get(OTHER, 0.0) / busy if busy else None,
            "other_ops_ms": sorted(ops.items(), key=lambda kv: -kv[1])[:8],
            "unnamed_ms": unnamed}


def fused_hlo(engine, mix: Dict, params) -> str:
    """The compiled text of the engine's fused decode program, at the
    shapes ``serve.warm`` compiles it (a persistent-cache hit)."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_cache

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    B = mix["slots"]
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    cache = jax.eval_shape(lambda: init_cache(engine.cfg, B,
                                              engine.cache_len, dtype))
    row = jax.ShapeDtypeStruct((B,), jnp.int32)
    return engine._fns.fused.lower(
        jax.tree.map(spec, params), row, cache, row,
        spec(jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((2,), jnp.int32)).compile().as_text()


def dump(trace: Dict, path: str):
    """The slice from the start of the first program decode round in
    the window to the start of the next, each widened by 1 ms, as
    ``load`` gives it, with the window cut to that length: a small
    recorded trace for tests/test_scopes.py."""
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
             "fused": {k: [o for o in v if deepest_scope(o)]
                       for k, v in trace["fused"].items() if k in names},
             "spans": [list(s) for s in trace["spans"]
                       if lo <= s[1] <= hi and s[0] != tr.WINDOW_SPAN]
             + [[tr.WINDOW_SPAN, lo, hi, -1]]}
    with open(path, "w") as f:
        json.dump(small, f)


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


def main():
    import calibrate
    import run as bench_run
    import runner
    import serve
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
    hlo: List[str] = []
    warm = serve.warm

    def warm_and_keep(engine, mix, params, mark=lambda name: None):
        warm(engine, mix, params, mark)
        hlo.append(fused_hlo(engine, mix, params))
    serve.warm = warm_and_keep
    tr.load = lambda path: load(path, hlo[0])
    run = runner.run_cell(cell, cfg, traffic.load_mix(cell["traffic"]),
                          runner.limits(cell["name"]), seed=args.seed,
                          seconds=args.seconds, traced=True, meter=meter,
                          t_process=bench_run.T_PROCESS, devices=devs,
                          device_peaks=peaks(devs[0].device_kind))
    out = calibrate.readings(run, e2e, per_layer, True, meter)
    out.update(seed=args.seed, decode=decode_breakdown(run),
               leaf_ops=op_seconds(run.trace),
               named_gaps=idle_gaps(run.trace),
               idle_by_span=idle_by_span(run.trace),
               serve_spans=span_totals(run.trace))
    if args.dump:
        dump(run.trace, args.dump)
    print(json.dumps(out, default=float), flush=True)


if __name__ == "__main__":
    main()
