"""One run of one cell: weights, traffic, warm-up, the window, the
trace, then the reference. ``run_cell`` returns a ``Run`` that the
metric readers in ``bench/metrics/`` read."""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

import check
import serve
import tracing as tr
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
HARNESS_CANCELLED = "cancelled"


@dataclasses.dataclass
class Run:
    cell: Dict
    cfg: Dict
    z: Dict                   # arch.<architecture>.dims, and its name
    mix: Dict
    peaks: Dict
    t_process: float
    t_open: float
    t_close: float
    clients: List             # serve.Client, in sending order
    tokens_in_window: int
    events: List[tuple]       # the recorder's events, on the host
    compile_events: List      # compiles and cache hits inside the window
    peak_bytes: int
    trace: Optional[Dict] = None
    trace_stats: Optional[Dict] = None
    correct: bool = False
    compared: Dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    marks: List = dataclasses.field(default_factory=list)  # set-up phases
    reference_s: float = 0.0  # the reference's replay, after the window
    t_end: float = 0.0        # the harness stopped waiting for first tokens

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def fused_in_window(self):
        """Fused calls that started inside the window."""
        return [e for e in self.events if e[0] == "fused"
                and self.t_open <= e[3] < self.t_close]


def arch_module(cfg: Dict):
    return importlib.import_module("arch." + cfg["architecture"])


def limits(cell_name: str) -> Dict:
    """bench/limits/<cell>.json: the limit of each number compared."""
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return json.load(f)


def check_layout(arch, cfg, pcfg):
    """The weights the benchmark draws must be laid out as the program
    reads them: same tree, shapes and types as its own init."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    want = jax.eval_shape(lambda: init_params(pcfg, jax.random.PRNGKey(0),
                                              jnp.bfloat16))
    got = jax.eval_shape(lambda: arch.make_weights(cfg, 0))
    w = {jax.tree_util.keystr(p): (v.shape, v.dtype)
         for p, v in jax.tree_util.tree_leaves_with_path(want)}
    g = {jax.tree_util.keystr(p): (v.shape, v.dtype)
         for p, v in jax.tree_util.tree_leaves_with_path(got)}
    if w != g:
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"{sorted(set(w.items()) ^ set(g.items()))}")


def run_cell(cell: Dict, cfg: Dict, mix: Dict, lim: Dict, *, seed: int,
             seconds: float, traced: bool, meter, t_process: float, devices,
             device_peaks: Dict, fault=None, control: bool = False) -> Run:
    """One run. `fault(engine)`, if given, is called after the warm-up:
    the fault tests break the timed path there. `control` also replays
    the schedule in the control precision (fp8) first and scores its
    tokens against the reference (``run.replay["control_gaps"]``); the
    benchmark's own runs leave it off."""
    import jax
    from repro.configs.base import XSharePolicy
    from repro.serving import Engine

    marks = [("start", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    arch = arch_module(cfg)
    pcfg = arch.program_config(cfg)
    check_layout(arch, cfg, pcfg)
    params = arch.make_weights(cfg, seed)
    jax.block_until_ready(params)
    mark("weights")
    reqs = traffic.generate(mix, cfg["vocab_size"], seed, seconds)
    mark("traffic")
    engine = Engine(pcfg, params, policy=XSharePolicy(**cfg["xshare"]),
                    cache_len=mix["cache_len"],
                    decode_chunk=mix["decode_chunk"])
    fused_text = serve.warm(engine, mix, params, mark, keep_text=traced)
    if fault is not None:
        fault(engine)
    capture = tr.Capture(tr_delay(seconds), tr_length(seconds)) \
        if traced else None
    out = serve.serve(engine, mix, reqs, seconds, traced=traced,
                      on_window=(lambda t: capture.start()) if traced
                      else None)
    peak = int(devices[0].memory_stats()["peak_bytes_in_use"])
    events = out["recorder"].host()
    run = Run(cell=cell, cfg=cfg,
              z=dict(arch.dims(cfg), architecture=cfg["architecture"]),
              mix=mix,
              peaks=device_peaks, t_process=t_process,
              t_open=out["t_open"], t_close=out["t_close"],
              clients=out["clients"],
              tokens_in_window=out["tokens_in_window"], events=events,
              compile_events=meter.between(out["t_open"], out["t_close"]),
              peak_bytes=peak, t_end=out["t_end"],
              marks=marks + [("door_start", out["t_start"]),
                             ("window_open", out["t_open"])])
    if traced:
        path = capture.join()
        run.trace = tr.load(path, fused_text)
        capture.remove()
        run.trace_stats = trace_stats(run.trace)

    served = {c.req.prompt.tobytes(): list(c.stream.tokens)
              for c in out["clients"]}
    bad = [c for c in out["clients"]
           if c.reason not in ("completed", HARNESS_CANCELLED)]
    run.attempted, run.failed = len(out["clients"]), len(bad)
    # the program's state goes before the reference draws its weights
    del engine, params, out
    for a in jax.live_arrays():
        a.delete()

    picks = None
    if control:
        ref = arch.Reference(cfg, seed, mix["slots"], mix["cache_len"],
                             precision="fp8")
        picks = check.replay(ref, events, served, mix["slots"],
                             mix["check_steps"])["argmax"]
        ref.close()
    t_ref = time.perf_counter()
    ref = arch.Reference(cfg, seed, mix["slots"], mix["cache_len"])
    rep = check.replay(ref, events, served, mix["slots"],
                       mix["check_steps"], control=picks)
    ref.close()
    run.reference_s = time.perf_counter() - t_ref
    run.compared = compare(rep, run.failed, lim)
    run.correct = all(c["value"] <= c["limit"]
                      for c in run.compared.values())
    run.replay = rep
    return run


def compare(rep: Dict, failed: int, lim: Dict) -> Dict:
    gaps = rep["gaps"]
    return {
        "logit_gap_max": {"value": float(gaps.max()) if len(gaps)
                          else float("inf"),
                          "limit": lim["logit_gap_max"]},
        "tokens_checked_short": {
            "value": int(lim["tokens_checked_min"] - len(gaps)),
            "limit": 0},
        "tokens_missing": {"value": int(rep["missing"]), "limit": 0},
        "requests_failed": {"value": int(failed), "limit": 0},
    }


def tr_delay(seconds: float) -> float:
    return min(2.0, seconds / 4)


def tr_length(seconds: float) -> float:
    return min(4.0, seconds / 2)


def trace_stats(trace: Dict) -> Dict:
    lo, hi = tr.window(trace)
    busy = tr.busy_ns(trace)
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "breakdown": {"device_ops": [[n, s] for n, s in
                                         tr.op_seconds(trace)],
                          "idle_gaps": [[n, s] for n, s in
                                        tr.idle_gaps(trace)]}}


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between order statistics); a missing
    value (+inf) sorts last and reads as 1e9."""
    v = np.asarray(values, float)
    if not len(v):
        return float("nan")
    return float(np.percentile(np.where(np.isfinite(v), v, 1e9), q))
