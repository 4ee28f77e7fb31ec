"""Runs one benchmark cell once and prints its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name:
``BENCHMARK.json`` names them, ``bench/configs/<config>.json`` holds the
configuration, ``bench/traffic/<mix>.json`` the mix and
``bench/metrics/<metric>.py`` the reader of each metric. The last line
of stdout is one JSON object; the numbers compared to decide `correct`
are also the last lines of stderr. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    cell = cells[name]
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        cfg = json.load(f)

    def reports(m):
        return "workloads" not in m or name in m["workloads"]
    return (bench, cell, cfg, [m for m in bench["end_to_end"] if reports(m)],
            [m for m in bench["per_layer"] if reports(m)])


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(n: int):
    """The devices, or exit non-zero (no result line) off the TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.stderr.write(f"bench: needs {n} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)\n")
        raise SystemExit(3)
    return devs[:n]


def enable_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), for every
    compile however short, so that only a cell's first run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileMeter:
    """XLA compiles and persistent-cache hits, timestamped, through
    jax.monitoring (as the program's chip smoke counts them)."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(("compile", time.perf_counter(), fun_name,
                                secs))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append(("cache_hit", time.perf_counter(), "", 0.0))

    def between(self, t0, t1):
        return [e for e in self.events if t0 <= e[1] < t1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, cfg, e2e, per_layer = load_cell(args.workload)

    enable_cache()
    devs = require_chips(cell["chips"])
    meter = CompileMeter()

    import runner
    import traffic
    from peaks import peaks
    run = runner.run_cell(cell, cfg, traffic.load_mix(cell["traffic"]),
                          runner.limits(cell["name"]), seed=args.seed,
                          seconds=args.seconds, traced=bool(args.trace),
                          meter=meter, t_process=T_PROCESS, devices=devs,
                          device_peaks=peaks(devs[0].device_kind))
    metrics = {}
    for m in (per_layer if args.trace else e2e):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": run.peak_bytes}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = run.trace_stats["busy_s"]
        device["window_s"] = run.trace_stats["window_s"]
        line["breakdown"] = run.trace_stats["breakdown"]
    line["compared"] = run.compared
    for name, c in run.compared.items():
        sys.stderr.write(f"compared {name} {c['value']!r} limit "
                         f"{c['limit']!r}\n")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
