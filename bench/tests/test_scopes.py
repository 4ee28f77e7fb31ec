"""The per-scope reduction (tracing.py, reading.py and the readers of
the scope metrics): on hand-made traces whose answers are worked out by
hand, and on a recorded v5e slice of the decode cell with the program's
scopes and serve/ spans (``trace_v5e_scopes.json``: one decode round of
a traced run and the gap after it, written by ``scopes.py --dump``)."""
import json
import os
import types

import numpy as np
import pytest

import tiny  # first: puts bench/ on the path
import reading
import run as bench_run
import scopes
import tracing as tr

DECODE_METRICS = ("attention_ms.decode", "kv_write_ms.decode",
                  "moe_ms.decode", "xshare_route_ms.decode",
                  "idle_ms_per_round.decode")


def fake_run(trace, steps):
    """What the readers read of a run: the trace, and a fused event of
    `steps` decode steps at the index of each traced fused_decode call."""
    ok = np.ones((steps, 1), bool)
    return types.SimpleNamespace(trace=trace, events={
        c: ("fused", None, (None, ok)) for _, _, c in
        tr.spans_named(trace, "fused_decode")})


def decode_metrics(trace, steps):
    run = fake_run(trace, steps)
    return {m: bench_run.load_module("metrics", m).read(run)
            for m in DECODE_METRICS}


BODY = "jit(<lambda>)/while/body/closed_call/while/body"
DUS = f"{BODY}/dynamic_update_slice"
HAND = {
    "devices": {"/device:TPU:0": [
        ["while.1", 0, 510],
        ["fusion.1", 0, 100], ["fusion.2", 100, 150],
        ["select_dynamic-update-slice_fusion.3", 150, 250],
        ["fusion.4", 250, 270], ["gather.5", 270, 300],
        ["grouped_ffn.6", 300, 400], ["scatter.7", 400, 420],
        ["dynamic-slice_bitcast_fusion.8", 420, 470], ["fusion.9", 470, 480],
        ["fusion.10", 480, 500], ["fusion.11", 500, 505],
        ["fusion.1", 610, 990]]},
    "scopes": {
        "while.1": "jit(<lambda>)/while",
        "fusion.1": f"{BODY}/closed_call/attention/dot_general",
        "fusion.2": f"{BODY}/closed_call/attention/kv_write/jit(_where)/"
                    "select_n",
        "select_dynamic-update-slice_fusion.3": DUS,
        "fusion.4": f"{BODY}/closed_call/moe/route/exp",
        "gather.5": f"{BODY}/closed_call/moe/dispatch/gather",
        "grouped_ffn.6": f"{BODY}/closed_call/moe/experts/jit(grouped_ffn)/"
                         "pallas_call",
        "scatter.7": f"{BODY}/closed_call/moe/combine/scatter-add",
        "dynamic-slice_bitcast_fusion.8": f"{BODY}/squeeze",
        "fusion.9": f"{BODY}/closed_call/moe/add",
        "fusion.10": "jit(<lambda>)/closed_call/lm_head/dot_general",
        "fusion.11": "jit(<lambda>)/closed_call/sample/argmax"},
    # the cache update fuses into the layer scan's own write of its
    # output, whose metadata the fusion takes
    "fused": {"select_dynamic-update-slice_fusion.3": [
        f"{BODY}/dynamic_slice",
        f"{BODY}/closed_call/attention/kv_write/jit(_where)/select_n",
        f"{BODY}/closed_call/attention/kv_write/eq", DUS]},
    "spans": [["trace_window", 0, 1000, -1],
              ["serve/decode", 0, 530, -1], ["fused_decode", 0, 520, 4],
              ["serve/harvest", 530, 600, -1], ["serve/pump", 540, 590, -1],
              ["serve/publish", 545, 555, -1],
              ["serve/decode", 600, 1000, -1]],
}
SPANS = [(0, 520, 4)]


def test_deepest_scope():
    assert tr.deepest_scope("a/attention/kv_write/jit(_where)/x") == \
        "attention/kv_write"
    assert tr.deepest_scope("a/moe/route/b/moe/x") == "moe"
    assert tr.deepest_scope("a/moe/route/attention/x") == "attention"
    assert tr.deepest_scope("a/while/body/dynamic_slice") is None
    assert tr.deepest_scope("") is None


def test_hlo_op_names():
    hlo = """HloModule jit_f

%fused_computation.3 (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  %select.1 = bf16[4]{0} select(%c, %param_0, %param_0), metadata={op_name="jit(f)/attention/kv_write/select_n"}
  ROOT %dynamic-update-slice.2 = bf16[4]{0} dynamic-update-slice(%param_0, %select.1, %i), metadata={op_name="jit(f)/while/body/dynamic_update_slice"}
}

ENTRY %main.9 (Arg_0.1: bf16[4]) -> bf16[4] {
  %Arg_0.1 = bf16[4]{0} parameter(0)
  %copy.4 = bf16[4]{0} copy(%Arg_0.1), metadata={op_name="jit(f)/moe/route/copy"}
  ROOT %select_dynamic-update-slice_fusion.3 = bf16[4]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/while/body/dynamic_update_slice"}
}
"""
    own, fused = tr.hlo_op_names(hlo)
    assert own["copy.4"] == "jit(f)/moe/route/copy"
    assert own["select_dynamic-update-slice_fusion.3"] == \
        "jit(f)/while/body/dynamic_update_slice"
    assert "Arg_0.1" not in own
    assert fused == {"select_dynamic-update-slice_fusion.3": [
        "jit(f)/attention/kv_write/select_n",
        "jit(f)/while/body/dynamic_update_slice"]}


def test_scope_of():
    assert tr.scope_of(HAND, "fusion.2") == "attention/kv_write"
    assert tr.scope_of(HAND, "select_dynamic-update-slice_fusion.3") \
        == "attention/kv_write"
    assert tr.scope_of(HAND, "dynamic-slice_bitcast_fusion.8") == "other"
    assert tr.scope_of(HAND, "no.such") == "other"
    assert tr.scope_of(dict(HAND, fused={}),
                       "select_dynamic-update-slice_fusion.3") == "other"


def test_leaves():
    ops = [["while.1", 0, 100], ["a.1", 0, 40], ["b.2", 40, 100],
           ["c.3", 90, 130], ["d.4", 130, 130]]
    # while.1 holds a.1 and b.2; c.3 only overlaps b.2's end
    assert [o[0] for o in tr.leaves(ops)] == ["a.1", "b.2", "c.3", "d.4"]


def test_hand_charge():
    ch = tr.charge(HAND, SPANS)
    assert ch == {"attention": 100, "attention/kv_write": 150,
                  "moe/route": 20, "moe/dispatch": 30, "moe/experts": 100,
                  "moe/combine": 20, "other": 50, "moe": 10, "lm_head": 20,
                  "sample": 5}
    # every leaf ns inside the call is charged once; the loop's own 5 ns
    # after its last leaf are busy time that no scope holds
    assert sum(ch.values()) == 505
    assert tr.busy_first_ns(HAND, SPANS) == 510
    # five steps; rounds start at 0 and 600, the device busy 0-510
    assert decode_metrics(HAND, steps=5) == pytest.approx({
        "attention_ms.decode": 100 / 1e6 / 5,
        "kv_write_ms.decode": 150 / 1e6 / 5,
        "moe_ms.decode": 180 / 1e6 / 5,
        "xshare_route_ms.decode": 20 / 1e6 / 5,
        "idle_ms_per_round.decode": 90 / 1e6})


def test_hand_rounds_and_gaps():
    # 510-610: the harness names nothing there, the program's pump holds
    # its middle (560); 990-1000 lies in the second decode round
    assert tr.idle_gaps(HAND) == [("serve/pump", 100e-9),
                                  ("serve/decode", 10e-9)]
    # without the program's spans no span holds either gap
    harness_only = dict(HAND, spans=[s for s in HAND["spans"]
                                     if not s[0].startswith(tr.SERVE)])
    assert tr.idle_gaps(harness_only) == [("none", 100e-9), ("none", 10e-9)]
    assert tr.idle_ms_per_round(harness_only) is None
    # 510-530 in decode, 530-540 harvest, 540-545 pump, 545-555
    # publish, 555-590 pump, 590-600 harvest, 600-610 and 990-1000 the
    # next decode
    assert scopes.idle_by_span(HAND) == {
        "serve/decode": 40, "serve/harvest": 20, "serve/pump": 40,
        "serve/publish": 10}
    assert tr.innermost_serve_span(HAND, 555) == "serve/publish"
    assert tr.innermost_serve_span(HAND, 1001) is None


def test_hand_op_seconds_leave_out_containers():
    ops = dict(tr.op_seconds(HAND, top=100))
    assert "while.1" not in ops
    assert ops["fusion.1"] == pytest.approx(100e-9 + 380e-9)
    # the leaves inside the loop (505 ns) and fusion.1's second run
    assert sum(ops.values()) == pytest.approx(505e-9 + 380e-9)


def test_hand_new_scope_is_charged_by_its_reader():
    # a scope the program adds (here "moe/shared") is charged apart
    # where a reader names it, and to the scope above it where none does
    t = json.loads(json.dumps(HAND))
    t["scopes"]["fusion.9"] = f"{BODY}/closed_call/moe/shared/add"
    run = fake_run(t, steps=5)
    assert reading.decode_scope_ms(run)["moe"] == pytest.approx(10 / 1e6 / 5)
    per = reading.decode_scope_ms(run, tr.SCOPES + ("moe/shared",))
    assert "moe" not in per
    assert per["moe/shared"] == pytest.approx(10 / 1e6 / 5)
    # the MoE layer's reading holds it either way
    assert decode_metrics(t, steps=5)["moe_ms.decode"] == \
        pytest.approx(180 / 1e6 / 5)


def test_scope_readers_find_nothing_without_names():
    # a trace loaded without the compiled text, or a run without a
    # trace, gives the scope readers nothing to read
    bare = dict(HAND, scopes={}, fused={})
    got = decode_metrics(bare, steps=5)
    assert [m for m, v in got.items() if v is not None] == \
        ["idle_ms_per_round.decode"]
    empty = types.SimpleNamespace(trace=None, events={})
    assert all(bench_run.load_module("metrics", m).read(empty) is None
               for m in DECODE_METRICS)


RECORDED = os.path.join(tiny.HERE, "trace_v5e_scopes.json")


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded v5e trace with scopes")
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_charge_covers_the_calls(recorded):
    spans = tr.spans_named(recorded, "fused_decode")
    assert len(spans) == 1
    ch = tr.charge(recorded, spans)
    busy = tr.busy_first_ns(recorded, spans)
    # each leaf op is charged once; the loops' own time between them is
    # all that the charge leaves out
    assert busy * 0.98 <= sum(ch.values()) <= busy
    assert set(ch) <= set(tr.SCOPES) | {"other"}
    for scope in ("attention", "attention/kv_write", "moe/route",
                  "moe/dispatch", "moe/experts", "moe/combine", "lm_head",
                  "sample"):
        assert ch.get(scope, 0) > 0, scope


def test_recorded_metrics(recorded):
    # one fused call of 8 steps; the readings of this slice, recorded on
    # a TPU v5 lite, within a tenth of a per cent
    got = decode_metrics(recorded, steps=8)
    assert got == pytest.approx({
        "attention_ms.decode": 5.628, "kv_write_ms.decode": 10.896,
        "moe_ms.decode": 4.522, "xshare_route_ms.decode": 0.533,
        "idle_ms_per_round.decode": 12.870}, rel=1e-3)


def test_recorded_rounds_and_gaps(recorded):
    gaps = tr.idle_gaps(recorded)
    named = sum(d for n, d in gaps if n.startswith("serve/"))
    assert named >= 0.9 * sum(d for _, d in gaps)
    # the split of the idle time among the host phases sums to it
    lo, hi = tr.window(recorded)
    idle = (hi - lo) - tr.busy_ns(recorded)
    assert sum(scopes.idle_by_span(recorded).values()) == \
        pytest.approx(idle)
    # no op that holds others heads the breakdown
    top = tr.op_seconds(recorded)
    assert not any(n.startswith("while") for n, _ in top)
