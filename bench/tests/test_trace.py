"""The reduction from a trace to numbers: on a hand-made trace whose
answers are worked out by hand, and on a slice of a real v5e trace of
the decode cell (``trace_v5e.json``: 0.8 s recorded by calibrate.py
--dump-trace)."""
import json
import os

import pytest

import tiny
import tracing as tr

HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 0, 100], ["grouped_ffn.3", 100, 250],
        ["fusion.1", 240, 300], ["copy.2", 500, 600],
        ["grouped_ffn.3", 650, 700], ["fusion.9", 980, 1200],
        ["fusion.12", 1100, 1150]]},
    "spans": [["trace_window", 0, 1000, -1],
              ["fused_decode", 0, 320, 4], ["harvest_gap", 320, 480, -1],
              ["prefill", 480, 720, 7], ["generator", 700, 1000, -1]],
}


def test_op_name():
    # the TPU names an op by its whole instruction; operands are not it
    assert tr.op_name("%fusion.7 = bf16[8,1024]{1,0} fusion(bf16[8,1024] "
                      "%grouped_ffn.11), kind=kLoop") == "fusion.7"
    assert tr.op_name("%grouped_ffn.11 = bf16[8,1024] custom-call(...)") \
        == "grouped_ffn.11"
    assert tr.op_name("while.100") == "while.100"
    assert tr.op_operands(
        "%grouped_ffn.11 = bf16[8,1024]{1,0:T(8,128)(2,1)} custom-call("
        "s32[4]{0} %copy-done.30, bf16[32,1024,512]{2,1,0} "
        "%dynamic-slice_bitcast_fusion.7.remat2), metadata={op_name="
        "\"jit(f)\"}") == ["copy-done.30",
                          "dynamic-slice_bitcast_fusion.7.remat2"]


def test_kernel_time_counts_its_weight_copies():
    # the kernel's weights (operands 3 to 5) come from copies made just
    # before it; a copy of the same name from an earlier call, an op
    # that feeds another operand and a later copy do not count
    t = {"devices": {"/device:TPU:0": [
            ["ds.1", 0, 40], ["ds.1", 100, 130], ["ds.2", 130, 150],
            ["x.5", 150, 160], ["grouped_ffn.3", 160, 200],
            ["ds.1", 210, 300]]},
         "operands": {"grouped_ffn.3": ["a", "b", "x.5", "ds.1", "ds.2",
                                        "gte.9"]},
         "spans": [["trace_window", 0, 1000, -1],
                   ["fused_decode", 90, 320, 4]]}
    spans = tr.spans_named(t, "fused_decode")
    assert tr.ops_by_call(t, spans, "grouped_ffn") == {4: [40]}
    assert tr.ops_by_call(t, spans, "grouped_ffn", (3, 4, 5)) == \
        {4: [40 + 30 + 20]}


def test_hand_trace():
    # busy: [0,300] + [500,600] + [650,700] + [980,1000] = 470 of 1000
    assert tr.window(HAND) == (0, 1000)
    assert tr.busy_ns(HAND) == 470
    assert tr.union([(0, 100), (100, 250), (240, 300)], 0, 1000) == \
        [(0, 300)]
    ops = dict(tr.op_seconds(HAND))
    assert ops["grouped_ffn.3"] == pytest.approx(200e-9)
    assert "fusion.9" not in ops    # it holds fusion.12: a container
    alone = dict(HAND, devices={"/device:TPU:0": [["fusion.9", 980, 1200]]})
    # clipped at 1000
    assert tr.op_seconds(alone) == pytest.approx([("fusion.9", 20e-9)])
    spans = tr.spans_named(HAND, "fused_decode") + \
        tr.spans_named(HAND, "prefill")
    assert tr.ops_by_call(HAND, spans, "grouped_ffn") == {4: [150],
                                                         7: [50]}
    assert tr.busy_within_ns(HAND, tr.spans_named(HAND, "prefill")) == 150
    # gaps: [300,500] in no span the harness or the program names ->
    # none; [700,980] under the generator; [600,650] in prefill
    assert tr.idle_gaps(HAND) == [("generator", 280e-9),
                                  ("none", 200e-9),
                                  ("prefill", 50e-9)]


RECORDED = os.path.join(tiny.HERE, "trace_v5e.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded v5e trace")
def test_recorded_v5e_trace():
    with open(RECORDED) as f:
        t = json.load(f)
    lo, hi = tr.window(t)
    busy = tr.busy_ns(t)
    assert 0 < busy <= hi - lo
    # the leaf ops cover the busy union on one chip, less the loops' own
    # time between them
    leaf = sum(s for _, s in tr.op_seconds(t, top=10**6)) * 1e9
    assert 0.99 * busy <= leaf <= busy + 1
    spans = tr.spans_named(t, "fused_decode")
    per = tr.ops_by_call(t, spans, "grouped_ffn")
    assert per and all(len(d) == len(next(iter(per.values())))
                       for d in per.values())
    gaps = tr.idle_gaps(t)
    assert gaps and all(n in tr.HARNESS_SPANS + ("none",) for n, _ in gaps)
