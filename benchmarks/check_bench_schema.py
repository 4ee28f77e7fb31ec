"""Sanity-check BENCH_*.json artifacts before CI uploads them.

Benchmarks persist machine-read metrics (BENCH_dispatch.json,
BENCH_spec.json, BENCH_ep.json, BENCH_robustness.json) that downstream
tooling and the README tables consume. A refactor that silently renames a key, emits NaN, or drops a
section would still "pass" the benchmark run — this checker fails the
CI job instead.

Two layers:

  * structural — every file is a JSON object whose leaves are finite
    numbers / strings / bools / null (no NaN/inf: ``json.dump`` writes
    them as non-standard tokens many parsers reject);
  * per-file contracts (SPECS) — required key paths with value
    predicates, e.g. the robustness artifact must carry a
    ``kill_recover`` section with ``lost_requests == 0`` and
    ``bit_identical == true``.

Usage: ``python -m benchmarks.check_bench_schema [files...]``
(defaults to every BENCH_*.json at the repo root; a file listed in
SPECS but absent on disk is skipped — each CI job produces only its
own artifact).
"""
from __future__ import annotations

import glob
import json
import math
import os
import sys
from typing import Any, Callable, Dict, List, Tuple

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


def _num(lo: float = None, hi: float = None) -> Callable[[Any], bool]:
    def check(v):
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            return False
        if math.isnan(v) or math.isinf(v):
            return False
        return (lo is None or v >= lo) and (hi is None or v <= hi)
    return check


def _is(val) -> Callable[[Any], bool]:
    return lambda v: v == val


def _count_map(v) -> bool:
    return isinstance(v, dict) and all(
        isinstance(k, str) and isinstance(n, int) and n >= 0
        for k, n in v.items())


# required key paths ("a.b.c") -> predicate, per artifact
SPECS: Dict[str, Dict[str, Callable[[Any], bool]]] = {
    "BENCH_dispatch.json": {
        "dispatch.speedup": _num(lo=0.0),
        "dispatch.sorted_wall_ms": _num(lo=0.0),
        "dispatch.einsum_wall_ms": _num(lo=0.0),
        "dispatch.sorted_vs_einsum_err": _num(lo=0.0),
    },
    "BENCH_spec.json": {
        # the speculative-decoding acceptance criteria, machine-checked:
        # scheduler-spec must beat plain decoding in the memory-bound
        # OTPS model, stay lossless (token-exact, incl. mixed traffic),
        # and hierarchical selection must activate fewer experts than
        # naive per-request top-k
        "spec.speedup": _num(lo=1.0),
        "spec.speedup_wall": _num(lo=0.0),
        "spec.acceptance_rate": _num(0.0, 1.0),
        "spec.drafted": _num(lo=1),
        "spec.tokens_per_round": _num(lo=0.0),
        "spec.token_exact_vs_plain": _is(True),
        "spec.token_exact_vs_lockstep": _is(True),
        "spec.token_exact_mixed": _is(True),
        "spec.activated_hier": _num(lo=0.0),
        "spec.activated_naive": _num(lo=0.0),
        "spec.activated_ratio": _num(0.0, 1.0),
        "spec.spec_budget_exhausted": _num(lo=0),
    },
    "BENCH_ep.json": {
        # the expert-parallel execution acceptance criteria: the
        # measured shard_map path must agree with the single-device
        # sorted reference within ep.EP_TOL_EPS, and Algorithm 6 + hot-expert
        # replication must cut measured peak-shard executed rows >= 2x
        # vs baseline routing at batch 16 (mean over decode steps), on
        # the 8-shard mesh that bound was set for
        "ep.batch": _is(16),
        "ep.shards": _is(8),
        "ep.steps": _num(lo=1),
        "ep.matches_single_device": _is(True),
        "ep.peak_rows_ratio": _num(lo=2.0),
        "ep.peak_rows_ratio_alg6": _num(lo=0.0),
        "ep.a2a_bytes_baseline": _num(lo=0.0),
        "ep.a2a_bytes_xshare": _num(lo=0.0),
        "ep.replication_factor": _num(lo=1.0),
        "ep.rebalances": _num(lo=0),
        "ep.rebalances_skipped": _num(lo=0),
        # speculative verify-batch shape B x (1 + L_s) must match too,
        # and not regress past baseline peak rows
        "ep.spec_peak_rows_ratio": _num(lo=1.0),
        "ep.spec_matches_single_device": _is(True),
    },
    "BENCH_robustness.json": {
        "robustness.survival_rate": _num(0.0, 1.0),
        "robustness.shed_breakdown": _count_map,
        "robustness.p99_latency_s": _num(lo=0.0),
        "robustness.chaos_otps_ratio": _num(lo=0.0),
        "robustness.fault_free.otps": _num(lo=0.0),
        "robustness.campaigns": lambda v: isinstance(v, list) and v,
        # the crash-tolerance acceptance criteria, machine-checked
        "robustness.kill_recover.lost_requests": _is(0),
        "robustness.kill_recover.corrupt_gaps": _is(0),
        "robustness.kill_recover.bit_identical": _is(True),
        "robustness.kill_recover.replay_fidelity": _num(0.0, 1.0),
        "robustness.kill_recover.recovery_wall_s": _num(lo=0.0),
        "robustness.kill_recover.snapshots_written": _num(lo=0),
        "robustness.kill_recover.resumed": _num(lo=0),
    },
}


def _walk(obj, path: str, errors: List[str]) -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            if not isinstance(k, str):
                errors.append(f"{path}: non-string key {k!r}")
            _walk(v, f"{path}.{k}" if path else str(k), errors)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _walk(v, f"{path}[{i}]", errors)
    elif isinstance(obj, float) and (math.isnan(obj) or math.isinf(obj)):
        errors.append(f"{path}: non-finite number {obj!r}")
    elif obj is not None and not isinstance(obj, (str, int, float, bool)):
        errors.append(f"{path}: non-JSON leaf {type(obj).__name__}")


def _lookup(obj, dotted: str) -> Tuple[bool, Any]:
    cur = obj
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return False, None
        cur = cur[part]
    return True, cur


def check_file(path: str) -> List[str]:
    """All schema violations for one artifact (empty = clean)."""
    errors: List[str] = []
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    except ValueError as e:
        return [f"{path}: invalid JSON ({e})"]
    if not isinstance(data, dict):
        return [f"{path}: top level must be an object"]
    _walk(data, "", errors)
    for dotted, pred in SPECS.get(os.path.basename(path), {}).items():
        found, val = _lookup(data, dotted)
        if not found:
            errors.append(f"{path}: missing required key {dotted}")
        elif not pred(val):
            errors.append(f"{path}: {dotted} = {val!r} fails its contract")
    return [f"{path}: {e}" if not e.startswith(path) else e
            for e in errors]


def main(argv: List[str]) -> int:
    files = argv or sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    if not files:
        print("check_bench_schema: no BENCH_*.json artifacts found",
              file=sys.stderr)
        return 1
    failures: List[str] = []
    for path in files:
        errs = check_file(path)
        failures.extend(errs)
        print(f"{os.path.basename(path)}: "
              f"{'OK' if not errs else f'{len(errs)} violation(s)'}")
    for e in failures:
        print(f"  {e}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
