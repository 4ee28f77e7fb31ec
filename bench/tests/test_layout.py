"""Every cell of BENCHMARK.json resolves to its files by name; the
traffic is the same for a seed and differs between seeds only in order
and tokens; every prompt length the traffic draws is warmed."""
import collections
import json
import os

import numpy as np
import pytest

import tiny
import traffic
from arch import moe_transformer  # noqa: F401

ROOT = os.path.dirname(tiny.BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def cell(name):
    return next(w for w in BENCH["workloads"] if w["name"] == name)


def config(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    import importlib
    import run
    w = cell(name)
    entry, cfg = config(w["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    arch = importlib.import_module("arch." + cfg["architecture"])
    arch.program_config(cfg)
    traffic.load_mix(w["traffic"])
    lim = json.load(open(os.path.join(tiny.BENCH, "limits", name + ".json")))
    assert {"logit_gap_max", "tokens_checked_min"} <= set(lim)
    e2e = [m for m in BENCH["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per = [m for m in BENCH["per_layer"]
           if "workloads" not in m or name in m["workloads"]]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert per
    for m in e2e + per:
        assert callable(run.load_module("metrics", m["name"]).read)
    for m in per:
        assert m["moves"] in [x["name"] for x in e2e], (m["name"], name)


@pytest.mark.parametrize("name", CELLS)
def test_traffic_deterministic_and_warmed(name):
    w = cell(name)
    _, cfg = config(w["config"])
    mix = traffic.load_mix(w["traffic"])
    a = traffic.generate(mix, cfg["vocab_size"], 2**31 + 12345, 2.0)
    b = traffic.generate(mix, cfg["vocab_size"], 2**31 + 12345, 2.0)
    c = traffic.generate(mix, cfg["vocab_size"], 987654321, 2.0)
    assert [(r.max_new, r.due_s, r.prompt.tobytes()) for r in a] == \
        [(r.max_new, r.due_s, r.prompt.tobytes()) for r in b]
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in c]
    n = min(len(a), len(c)) // mix["block"] * mix["block"]
    assert n
    for attr in ("max_new", "topic"):
        assert collections.Counter(getattr(r, attr) for r in a[:n]) == \
            collections.Counter(getattr(r, attr) for r in c[:n])
    warmed = {t for _, t in traffic.warm_shapes(mix)}
    assert {r.prompt.shape[0] for r in a + c} <= warmed
    o = mix["output_len"]
    lengths = np.array([r.max_new for r in a])
    assert lengths.min() >= o["min"] and lengths.max() <= o["max"]
    biggest = max(r.prompt.shape[0] + r.max_new for r in a + c)
    assert biggest <= mix["cache_len"]


def test_additions_need_no_edit(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric are
    new files plus new BENCHMARK.json entries: in a copy of the
    benchmark, the harness finds each by name without an edit to a file
    it already had."""
    import shutil
    import subprocess
    import sys
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    b = tmp_path / "bench"
    shutil.copy(b / "configs" / "granite-moe-1b-a400m.json",
                b / "configs" / "new-config.json")
    mix = json.load(open(b / "traffic" / "chat-poisson.json"))
    mix["rate_per_s"] = 1.5
    json.dump(mix, open(b / "traffic" / "new-mix.json", "w"))
    shutil.copy(b / "limits" / "granite-moe-1b-a400m.chat-poisson.json",
                b / "limits" / "new-config.new-mix.json")
    (b / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="new-config",
                                 file="bench/configs/new-config.json"))
    bench["workloads"].append({"name": "new-config.new-mix",
                               "config": "new-config", "traffic": "new-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "tpot_p95_ms",
                               "workloads": ["new-config.new-mix"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    probe = (
        "import sys; sys.path.insert(0, 'bench'); import run, runner, "
        "traffic\n"
        "_, cell, cfg, e2e, per = run.load_cell('new-config.new-mix')\n"
        "mix = traffic.load_mix(cell['traffic'])\n"
        "assert mix['rate_per_s'] == 1.5, mix\n"
        "assert runner.limits(cell['name'])\n"
        "names = [m['name'] for m in per]\n"
        "assert 'new_metric' in names, names\n"
        "assert run.load_module('metrics', 'new_metric').read(None) == 42\n"
        "print('ok', cfg['name'])\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok granite-moe-1b-a400m"


def test_architecture_and_counter_need_no_edit(tmp_path):
    """A new architecture module that counts its own work, and a reader
    of a program counter no other reader reads, are new files: in a copy
    of the benchmark they work through runner and work.py at a tiny size
    on the CPU without an edit to a file the harness had."""
    import shutil
    import subprocess
    import sys
    b = tmp_path / "bench"
    shutil.copytree(tiny.BENCH, b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(b / "tests" / "stub_arch.py", b / "arch" / "stub_arch.py")
    shutil.copy(b / "tests" / "stub_selected_set.py",
                b / "metrics" / "stub_selected_set.py")
    probe = (
        "import sys; sys.path.insert(0, 'bench/tests')\n"
        "import numpy as np, tiny, run, work\n"
        "cfg = dict(tiny.CFG, architecture='stub_arch')\n"
        "r = tiny.run(tiny.CLOSED, cfg=cfg)\n"
        "assert r.correct, r.compared\n"
        "assert r.z['architecture'] == 'stub_arch'\n"
        "assert work.token_flops(r.z, 5) == 7 * 2 + 5\n"
        "z = dict(r.z); z.pop('architecture')\n"
        "assert work.token_flops(z, 5) != 7 * 2 + 5\n"
        "got = run.load_module('metrics', 'stub_selected_set').read(r)\n"
        "sel = [e[5]['selected_set'] for e in r.fused_in_window()]\n"
        "assert sel and all(s.shape == e[2][3].shape for s, e in\n"
        "                   zip(sel, r.fused_in_window()))\n"
        "assert got == float(np.concatenate(sel).mean()), got\n"
        "assert 0 < got <= cfg['num_experts'], got\n"
        "print('ok', got)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split()[0] == "ok"
