"""The harness's trace readers (tracing.py and runner.trace_stats) hold
their values: each is pinned on the hand-made trace of test_trace.py and
on the recorded v5e slice (``trace_v5e.json``), as it reads with the
readers as they stand. A change to how a reader counts has to change a
number here too, and says so."""
import json
import os

import pytest

import tiny  # first: puts bench/ on the path
import runner
import tracing as tr
from test_trace import HAND

RECORDED = os.path.join(tiny.HERE, "trace_v5e.json")


def read_all(t):
    spans = tr.spans_named(t, "fused_decode")
    stats = runner.trace_stats(t)
    return {
        "window": tr.window(t), "busy_ns": tr.busy_ns(t),
        "op_seconds": tr.op_seconds(t), "idle_gaps": tr.idle_gaps(t),
        "fused_spans": spans,
        "kernel": {c: (len(d), sum(d)) for c, d in tr.ops_by_call(
            t, spans, "grouped_ffn", (3, 4, 5)).items()},
        "busy_within_ns": tr.busy_within_ns(t, spans),
        "busy_s": stats["busy_s"], "window_s": stats["window_s"],
    }


def test_hand_readers_pinned():
    got = read_all(HAND)
    assert got["window"] == (0, 1000)
    assert got["busy_ns"] == 470
    # fusion.9 holds fusion.12, so it counts as a container
    assert got["op_seconds"] == pytest.approx(
        [("grouped_ffn.3", 2e-07), ("fusion.1", 1.6e-07),
         ("copy.2", 1e-07)])
    assert got["idle_gaps"] == pytest.approx(
        [("generator", 2.8e-07), ("none", 2e-07), ("prefill", 5e-08)])
    assert got["fused_spans"] == [(0, 320, 4)]
    assert got["kernel"] == {4: (1, 150)}
    assert got["busy_within_ns"] == 300
    assert (got["busy_s"], got["window_s"]) == pytest.approx((4.7e-07,
                                                              1e-06))


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded v5e trace")
def test_recorded_readers_pinned():
    with open(RECORDED) as f:
        got = read_all(json.load(f))
    assert got["window"] == (42689338.0, 317314631.0)
    assert got["busy_ns"] == 264216832.0
    # leaf ops only, no while.* container
    assert got["op_seconds"][:6] == pytest.approx(
        [("select_dynamic-update-slice_fusion.12", 0.021809437),
         ("select_dynamic-update-slice_fusion.14", 0.021802435),
         ("select_dynamic-update-slice_fusion.15", 0.021780882),
         ("select_dynamic-update-slice_fusion.13", 0.021779745),
         ("copy.611", 0.010861362), ("copy.612", 0.01084347)])
    # recorded before the program had spans of its own
    assert [n for n, _ in got["idle_gaps"]] == ["none"] * 7 + \
        ["fused_decode"] * 3
    assert [s for _, s in got["idle_gaps"][:3]] == pytest.approx(
        [0.006729015, 0.001164588, 0.001054178])
    assert got["fused_spans"] == [(43877218.0, 297314631.0, 9)]
    assert got["kernel"] == {9: (192, 11461021.0)}
    assert got["busy_within_ns"] == 251393050.0
    assert (got["busy_s"], got["window_s"]) == pytest.approx(
        (0.264216832, 0.274625293))
