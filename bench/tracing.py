"""The profiler trace of a traced run, and its reduction to numbers.

``capture`` runs the profiler over a slice of the window in a thread of
its own and marks the slice with a host span named ``trace_window``.
``load`` reads the ``.xplane.pb`` file into plain lists (device
operations per chip, host spans by name), and the functions below reduce
those lists; they know nothing of the profiler, so a small recorded
trace checks them (tests/test_trace.py).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Tuple

WINDOW_SPAN = "trace_window"
HOST_SPANS = ("prefill", "insert", "fused_decode", "submit", "generator",
              WINDOW_SPAN)


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


def load(path: str) -> Dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]},
        "operands": {kernel op name: [operand op names]},
        "spans": [(name, start_ns, end_ns, call)]} from an xplane file.
    Device operations are the events of each TPU plane's "XLA Ops" line,
    named by ``op_name``; a custom call (a Pallas kernel) also keeps its
    operands; `call` is the recorder's event index of a span's call (-1
    if none)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    operands: Dict[str, List[str]] = {}
    spans: List[Tuple[str, float, float]] = []
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
    return {"devices": devices, "operands": operands,
            "spans": sorted(spans, key=lambda s: s[1])}


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


def busy_ns(trace: Dict) -> float:
    """Device busy time in the window, averaged over the chips."""
    lo, hi = window(trace)
    per = [sum(b - a for a, b in union([(s, e) for _, s, e in ops], lo, hi))
           for ops in trace["devices"].values()]
    if not per:
        raise RuntimeError("the trace holds no device operations")
    return sum(per) / len(per)


def op_seconds(trace: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """Device time per operation name over the window (summed over
    chips, then averaged), largest first."""
    lo, hi = window(trace)
    tot: Dict[str, float] = {}
    for ops in trace["devices"].values():
        for name, s, e in ops:
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
    plane = sorted(trace["devices"])[0]
    ops = trace["devices"][plane]
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


SERVING = ("prefill", "insert", "fused_decode", "submit")


def idle_gaps(trace: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches with no operation on the device (first
    chip), each named by what the host was doing in its middle: the
    serving thread's span there, else the load generator's, else
    "harvest" (the scheduler's own host code between calls)."""
    lo, hi = window(trace)
    plane = sorted(trace["devices"])[0]
    busy = union([(s, e) for _, s, e in trace["devices"][plane]], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        here = [n for n, s, e, _ in trace["spans"] if s <= mid <= e]
        name = next((n for n in SERVING if n in here),
                    "generator" if "generator" in here else "harvest")
        out.append((name, (b - a) / 1e9))
    return out
