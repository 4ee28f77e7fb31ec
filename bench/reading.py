"""Shared arithmetic of the per-layer readers: which recorded calls the
traced slice holds, what work each did, and the least time the chip
needs for it."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import check
import tracing as tr
import work

KERNEL = "grouped_ffn"
# grouped_ffn's operands: tile_eid, tile_valid, xs, w1, w3, w2. On the
# v5e the compiler slices each layer's expert weights into fast memory
# in ops of their own before the kernel; their time is counted with it.
WEIGHT_OPERANDS = (3, 4, 5)


def contexts(events: List[tuple], num_slots: int) -> Dict[int, List]:
    """{fused event index: [per step, the context length (positions
    seen, itself included) of each live slot]} over the whole run."""
    pos = np.zeros(num_slots, int)
    lengths: List[int] = []
    out: Dict[int, List] = {}
    for item in check.walk(events, num_slots):
        if item[0] == "prefill":
            lengths = [row.shape[0] for row in item[1]]
        elif item[0] == "slot":
            pos[item[1]] = lengths[item[2]]
        else:
            _, live, i, _ = item
            out.setdefault(i, []).append(pos[live] + 1)
            pos = pos + live
    return out


def traced_calls(run, span: str):
    """Spans of `span` wholly inside the traced slice, (start, end, call)."""
    return [s for s in tr.spans_named(run.trace, span) if s[2] >= 0]


def decode_scope_ms(run, scopes=tr.SCOPES):
    """{scope: device ms per decode step} of the fused decode calls in
    the traced slice (``tracing.charge`` over `scopes`, the rest under
    "other"), or None where there is no such call or the program's
    names were not kept."""
    if run.trace is None or not run.trace.get("scopes"):
        return None
    spans = traced_calls(run, "fused_decode")
    if not spans:
        return None
    steps = sum(run.events[c][2][1].shape[0] for _, _, c in spans)
    return {k: ns / 1e6 / steps
            for k, ns in tr.charge(run.trace, spans, scopes).items()}


def least_seconds(run, flops: float, nbytes: float) -> float:
    p = run.peaks
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def kernel_roofline(run, span: str, work_of) -> float:
    """Least time over kernel time of the grouped_ffn events inside the
    traced `span` calls, in %; a kernel's time includes the ops that
    copied its expert weights for it. work_of(event) gives one (pairs,
    experts) per kernel invocation the call makes, in order; a call
    whose count of kernel events differs is a fault of the reading and
    raises."""
    spans = traced_calls(run, span)
    if not spans:
        return None
    per_call = tr.ops_by_call(run.trace, spans, KERNEL, WEIGHT_OPERANDS)
    least, spent = 0.0, 0.0
    z = run.z
    for call, durs in per_call.items():
        jobs = work_of(run.events[call])
        if len(jobs) != len(durs):
            raise RuntimeError(f"call {call}: {len(durs)} {KERNEL} events "
                               f"for {len(jobs)} invocations")
        for pairs, experts in jobs:
            least += least_seconds(run, work.grouped_ffn_flops(z, pairs),
                                   work.grouped_ffn_bytes(z, pairs, experts))
        spent += sum(durs) / 1e9
    return 100.0 * least / spent if spent else None
