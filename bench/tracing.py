"""The profiler trace of a traced run, and its reduction to numbers.

``capture`` runs the profiler over a slice of the window in a thread of
its own and marks the slice with a host span named ``trace_window``.
``load`` reads the ``.xplane.pb`` file into plain lists (device
operations per chip, host spans by name), and the functions below reduce
those lists; they know nothing of the profiler, so small recorded
traces check them (tests/test_trace.py, tests/test_scopes.py).

The program names its own layers. Named scopes put "attention",
"attention/kv_write", "moe", "moe/route", ... into the op_name metadata
of each device instruction, and the scheduler's host phases are
profiler spans named "serve/..." whose arguments are its counters. On a
TPU v5e the profiler names a device op by its instruction text without
the metadata, so ``load`` takes each instruction's op_name from the
text of the compiled fused decode program, and keeps the program's
spans beside the harness's own.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "trace_window"
# The harness's host spans, in the order an idle gap outside every
# program span takes its name from them
HARNESS_SPANS = ("prefill", "insert", "fused_decode", "submit", "generator")
HOST_SPANS = HARNESS_SPANS + (WINDOW_SPAN,)
SERVE = "serve/"          # the program's own host spans
# The program's scopes that a device op is charged to (``charge``); a
# reader of a scope the program adds passes its own tuple.
SCOPES = ("attention", "attention/kv_write", "moe", "moe/route",
          "moe/dispatch", "moe/experts", "moe/combine", "lm_head", "sample")
OTHER = "other"


class Capture:
    """Starts the profiler `delay_s` after ``start()`` and stops it
    `length_s` later, from a thread of its own."""

    def __init__(self, delay_s: float, length_s: float):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.delay_s, self.length_s = delay_s, length_s
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.error = None

    def start(self):
        self.thread.start()

    def _run(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no event per Python call
        try:
            time.sleep(self.delay_s)
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                time.sleep(self.length_s)
            jax.profiler.stop_trace()
        except Exception as e:          # noqa: BLE001 - reported by join
            self.error = e

    def join(self) -> str:
        """Waits for the trace; returns the path of its xplane file."""
        self.thread.join()
        if self.error is not None:
            raise self.error
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        return paths[0]

    def remove(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def op_name(event_name: str) -> str:
    """The HLO instruction's own name. On the TPU an "XLA Ops" event is
    named by the whole instruction ("%grouped_ffn.11 = bf16[...]
    custom-call(...)"), whose operands name other instructions."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_operands(event_name: str) -> List[str]:
    """The names of the instructions an op reads, in operand order (the
    `%name` tokens after its own name)."""
    rest = event_name.split(" = ", 1)[1] if " = " in event_name else ""
    return re.findall(r"%([\w.\-]+)", rest)


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


def load(path: str, hlo_text: Optional[str] = None) -> Dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]},
        "operands": {kernel op name: [operand op names]},
        "spans": [(name, start_ns, end_ns, call)],
        "serve_args": [(name, start_ns, {arg: value})],
        "scopes": {instruction: op_name},
        "fused": {fusion: op_names fused into it}} from an xplane file.
    Device operations are the events of each TPU plane's "XLA Ops" line,
    named by ``op_name``; a custom call (a Pallas kernel) also keeps its
    operands. Spans are the harness's (`call` is the recorder's event
    index of a span's call, -1 if none) and the program's ``serve/``
    spans (call -1), whose arguments are kept in "serve_args". "scopes"
    and "fused" come from `hlo_text`, the compiled fused decode
    program, and are empty without it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    operands: Dict[str, List[str]] = {}
    spans: List[Tuple[str, float, float, int]] = []
    args = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = op_name(ev.name)
                        ops.append((name, ev.start_ns, ev.end_ns))
                        if " custom-call(" in ev.name \
                                and name not in operands:
                            operands[name] = op_operands(ev.name)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        call = dict(ev.stats).get("call", -1)
                        spans.append((ev.name, ev.start_ns, ev.end_ns,
                                      int(call)))
                    elif ev.name.startswith(SERVE):
                        spans.append((ev.name, ev.start_ns, ev.end_ns, -1))
                        args.append((ev.name, ev.start_ns, dict(ev.stats)))
    scopes, fused = hlo_op_names(hlo_text) if hlo_text else ({}, {})
    return {"devices": devices, "operands": operands,
            "spans": sorted(spans, key=lambda s: s[1]),
            "serve_args": sorted(args, key=lambda a: a[1]),
            "scopes": scopes, "fused": fused}


def window(trace: Dict) -> Tuple[float, float]:
    w = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if len(w) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0][1], w[0][2]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def first_plane(trace: Dict) -> List[Tuple[str, float, float]]:
    """The device operations of the first chip."""
    return trace["devices"][sorted(trace["devices"])[0]]


def busy_ns(trace: Dict) -> float:
    """Device busy time in the window, averaged over the chips."""
    lo, hi = window(trace)
    per = [sum(b - a for a, b in union([(s, e) for _, s, e in ops], lo, hi))
           for ops in trace["devices"].values()]
    if not per:
        raise RuntimeError("the trace holds no device operations")
    return sum(per) / len(per)


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


def op_seconds(trace: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """Device time per operation name over the window (summed over
    chips, then averaged), largest first, of the leaf ops only: a
    container's time is that of the ops it holds, counted already."""
    lo, hi = window(trace)
    tot: Dict[str, float] = {}
    for ops in trace["devices"].values():
        for name, s, e in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d
    n = max(1, len(trace["devices"]))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(name, ns / n / 1e9) for name, ns in ranked]


def spans_named(trace: Dict, name: str) -> List[Tuple[float, float, int]]:
    """(start, end, call) of the spans of `name` that lie wholly inside
    the window."""
    lo, hi = window(trace)
    return [(s, e, c) for n, s, e, c in trace["spans"]
            if n == name and s >= lo and e <= hi]


def ops_by_call(trace: Dict, spans, kernel: str,
                fed_by=()) -> Dict[int, List[float]]:
    """{call: [duration_ns, ...]} of the device operations (first chip)
    named `kernel` or `kernel.<n>` that start inside each of `spans`
    (start, end, call). To each duration is added that of the ops which
    produced the kernel's operands at the positions `fed_by`, each the
    last run of that op before the kernel inside the same span: the
    compiler may copy a kernel's inputs into fast memory in ops of their
    own, and that copy is then part of the kernel's work."""
    ops = first_plane(trace)
    starts = [s for _, s, _ in ops]
    operands = trace.get("operands", {})
    out = {}
    for a, b, call in spans:
        i = bisect.bisect_left(starts, a)
        j = bisect.bisect_right(starts, b)
        last: Dict[str, Tuple[float, float]] = {}
        durs = []
        for n, s, e in ops[i:j]:
            if n == kernel or n.startswith(kernel + "."):
                d = e - s
                names = operands.get(n, [])
                for k in fed_by:
                    if k < len(names) and names[k] in last:
                        ps, pe = last[names[k]]
                        d += pe - ps
                durs.append(d)
            last[n] = (s, e)
        out[call] = durs
    return out


def busy_within_ns(trace: Dict, spans) -> float:
    """Device busy time inside `spans`, averaged over the chips."""
    per = []
    for ops in trace["devices"].values():
        tot = 0.0
        for a, b, *_ in spans:
            tot += sum(y - x for x, y in union([(s, e) for _, s, e in ops],
                                                a, b))
        per.append(tot)
    return sum(per) / max(1, len(per))


def busy_first_ns(trace: Dict, spans) -> float:
    """Device busy time on the first chip inside `spans`."""
    busy = [(s, e) for _, s, e in first_plane(trace)]
    return sum(y - x for a, b, *_ in spans for x, y in union(busy, a, b))


def deepest_scope(op_name: str, scopes=SCOPES) -> Optional[str]:
    """The scope of `scopes` that ends deepest in an op_name path (the
    longer one where two end at the same component), or None."""
    parts = op_name.split("/")
    best, key = None, (-1, 0)
    for scope in scopes:
        sp = scope.split("/")
        for i in range(len(parts) - len(sp) + 1):
            if parts[i:i + len(sp)] == sp and (i + len(sp), len(sp)) > key:
                best, key = scope, (i + len(sp), len(sp))
    return best


def scope_of(trace: Dict, op: str, scopes=SCOPES) -> str:
    """The scope a device op is charged to: the deepest of `scopes` in
    its own op_name; for a fusion whose root lies in none, the scope
    most of its fused instructions name (the deeper on a tie); else
    "other"."""
    own = deepest_scope(trace.get("scopes", {}).get(op, ""), scopes)
    if own is not None:
        return own
    named = [s for s in (deepest_scope(n, scopes)
                         for n in trace.get("fused", {}).get(op, []))
             if s is not None]
    if not named:
        return OTHER
    count = collections.Counter(named)
    return max(count, key=lambda s: (count[s], s.count("/"), s))


def charge(trace: Dict, spans, scopes=SCOPES) -> Dict[str, float]:
    """{scope: device ns} of the leaf ops on the first chip inside
    `spans` (start, end, ...), each clipped to its span; ops under none
    of `scopes` are charged to "other"."""
    ops = leaves(first_plane(trace))
    starts = [s for _, s, _ in ops]
    scope: Dict[str, str] = {}
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b, *_ in spans:
        i = max(0, bisect.bisect_left(starts, a) - 1)
        for name, s, e in ops[i:bisect.bisect_right(starts, b)]:
            d = min(e, b) - max(s, a)
            if d > 0:
                if name not in scope:
                    scope[name] = scope_of(trace, name, scopes)
                out[scope[name]] += d
    return dict(out)


def innermost_serve_span(trace: Dict, t: float) -> Optional[str]:
    """The program span (``serve/...``) at time `t` that began last,
    i.e. the innermost of those that hold `t`."""
    here = [(s, n) for n, s, e, _ in trace["spans"]
            if n.startswith(SERVE) and s <= t <= e]
    return max(here)[1] if here else None


def idle_intervals(trace: Dict) -> List[Tuple[float, float]]:
    """The stretches of the window with no operation on the first
    chip."""
    lo, hi = window(trace)
    busy = union([(s, e) for _, s, e in first_plane(trace)], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_gaps(trace: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches with no operation on the device (first
    chip), each named by what the host was doing in its middle: the
    innermost program span (``serve/...``) there, else the harness's
    serving span, else the load generator's, else "none"."""
    gaps = sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        here = [n for n, s, e, _ in trace["spans"] if s <= mid <= e]
        name = innermost_serve_span(trace, mid) or next(
            (n for n in HARNESS_SPANS if n in here), "none")
        out.append((name, (b - a) / 1e9))
    return out


def idle_ms_per_round(trace: Dict, span: str = "serve/decode") -> Optional[
        float]:
    """Device idle time on the first chip between the starts of the
    first and the last program `span` that start inside the window,
    over the rounds that started in between, in ms."""
    lo, hi = window(trace)
    starts = sorted(s for n, s, _, c in trace["spans"]
                    if n == span and c == -1 and lo <= s <= hi)
    if len(starts) < 2:
        return None
    a, b = starts[0], starts[-1]
    return ((b - a) - busy_first_ns(trace, [(a, b)])) / 1e6 / (
        len(starts) - 1)
