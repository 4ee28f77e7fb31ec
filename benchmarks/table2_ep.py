"""Table 2 — expert-parallel deployment (DeepSeek-R1 geometry: 256
routed experts, top-8, 8 device groups), in two layers:

* the paper-metric simulation (full mode): baseline routing vs
  Algorithm 6 (k0=1, m_g=5) under teacher-forced decode — activated
  experts, peak per-group load, accuracy proxy (the original Table 2);

* a MEASURED-EXECUTION scoreboard: the shard_map EP executor
  (ep/executor.py) actually runs baseline routing, Algorithm 6, and
  Algorithm 6 + hot-expert replication on an 8-shard mesh (one shard
  per device group, the mesh the BENCH_ep.json contract is set for)
  over the first eight devices this process sees, in this process, at
  decode shape (B=16 requests, one token each) and at the speculative
  verify shape B x (1 + L_s). With fewer than eight devices it refuses
  to run. On the CPU the launcher provides the devices
  (XLA_FLAGS=--xla_force_host_platform_device_count=8, set before
  Python starts, as CI does). Scored per shard: rows the grouped GEMM
  actually executed (occupied tiles * block_t — at decode sizes this
  is dominated by active experts per shard, the quantity Algorithm 6
  bounds), real segment rows, and all-to-all bytes on the wire. Every
  executed step is checked against the single-device sorted reference
  within ep.EP_TOL_EPS.

Routing comes from the trained router (layer 0) over real token
embeddings of the heterogeneous eval sets — trained expert affinities,
not synthetic skew. Results persist to BENCH_ep.json at the repo root
(contract: benchmarks/check_bench_schema.py), wired into both CI jobs
via ``benchmarks.run --quick``.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

G = 8                  # device groups of the simulated Table 2
E, K = 256, 8
BLOCK_T = 8            # tile grid of the measured grouped GEMM
SPEC_LS = 3            # verify shape: B x (1 + L_s)
REPLICATE_HOT = 1      # replicate the hottest expert...
MAX_REPLICAS = 2       # ...two ways (decode segments are tile-sized:
                       # heavy replication just mints padding tiles)

BENCH_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                          "BENCH_ep.json")


def _routing_traces(cfg, params, fam, *, bs: int, steps: int,
                    shards: int):
    """Per-step routing decisions from the trained layer-0 router over
    real token embeddings: decode shape (bs, 1 token) and spec verify
    shape (bs, 1 + L_s), for baseline and Algorithm-6 policies with one
    device group per EP shard."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import DATASETS, eval_tokens
    from repro.configs.base import XSharePolicy
    from repro.models.model import embed_tokens
    from repro.models.moe import route

    toks = eval_tokens(fam, DATASETS, batch_per=bs // 4, seq=40)
    emb = embed_tokens(cfg, params, jnp.asarray(toks))
    layer = jax.tree_util.tree_map(lambda a: a[0],
                                   params["layers"]["moe"])
    pol_off = XSharePolicy(mode="off", num_groups=shards)
    pol_x = XSharePolicy(mode="ep", k0=1, m_g=4, num_groups=shards)
    out = {}
    for shape, width in (("dec", 1), ("spec", 1 + SPEC_LS)):
        xs, tr = [], {"off": ([], []), "alg6": ([], [])}
        hists = []
        for step in range(steps):
            pos = 8 + (step * width) % (40 - 8 - width)
            x = emb[:, pos:pos + width].reshape(-1, cfg.d_model)
            xs.append(np.asarray(x))
            for name, pol in (("off", pol_off), ("alg6", pol_x)):
                idx, w, _, _ = route(layer, x, cfg.moe, pol)
                tr[name][0].append(np.asarray(idx))
                tr[name][1].append(np.asarray(w))
            counts = np.zeros(E, np.int64)
            np.add.at(counts,
                      tr["alg6"][0][-1].reshape(-1).clip(0),
                      tr["alg6"][1][-1].reshape(-1) != 0)
            hists.append(counts)
        out[shape] = {
            "x": np.stack(xs).astype(np.float32),
            "idx_off": np.stack(tr["off"][0]).astype(np.int32),
            "w_off": np.stack(tr["off"][1]).astype(np.float32),
            "idx_x": np.stack(tr["alg6"][0]).astype(np.int32),
            "w_x": np.stack(tr["alg6"][1]).astype(np.float32),
            "hist": np.stack(hists).astype(np.float64),
        }
    return out


def _measure(params, traces, shards: int) -> dict:
    """Real shard_map execution on a mesh over this process's devices.

    Three executors per shape — baseline routing on the standard
    contiguous layout, Algorithm-6 routing on histogram-driven LPT
    placement, and the same plus hot-expert replication with
    between-step hysteresis rebalancing. Every step's output is checked
    against the single-device sorted reference within ep.EP_TOL_EPS.
    """
    import jax.numpy as jnp

    from repro.ep import (EP_TOL_EPS, EPExecutor, contiguous_placement,
                          ep_mismatch, plan_placement)
    from repro.models.dispatch import sorted_expert_ffn
    from repro.sharding import make_ep_mesh

    moe = params["layers"]["moe"]
    w1, w3, w2 = (jnp.asarray(moe[k][0], jnp.float32)
                  for k in ("w1", "w3", "w2"))
    S = shards
    mesh = make_ep_mesh(S)
    out = {}
    for shape, tr in traces.items():
        hist = tr["hist"]
        execs = {
            "off": EPExecutor(mesh, contiguous_placement(E, S),
                              block_t=BLOCK_T),
            "alg6": EPExecutor(mesh, plan_placement(hist[0], S),
                               block_t=BLOCK_T),
            "alg6_rep": EPExecutor(
                mesh,
                plan_placement(hist[0], S, replicate_hot=REPLICATE_HOT,
                               max_replicas=MAX_REPLICAS),
                block_t=BLOCK_T, replicate_hot=REPLICATE_HOT,
                max_replicas=MAX_REPLICAS),
        }
        rec = {m: {"tile_peak": [], "row_peak": [], "a2a": []}
               for m in execs}
        worst = 0.0
        steps = tr["x"].shape[0]
        for t in range(steps):
            x = jnp.asarray(tr["x"][t])
            for m, ex in execs.items():
                side = "off" if m == "off" else "x"
                idx = jnp.asarray(tr[f"idx_{side}"][t])
                w = jnp.asarray(tr[f"w_{side}"][t])
                if m != "off" and t > 0:
                    # between-step rebalance from the fresh histogram
                    # (hysteresis inside); replication is the only
                    # difference between alg6 and alg6_rep
                    ex.update_placement(hist[t])
                y, st = ex(x, w1, w3, w2, idx, w)
                ref = sorted_expert_ffn(x, w1, w3, w2, idx, w,
                                        block_t=BLOCK_T)
                worst = max(worst, ep_mismatch(y, ref))
                rec[m]["tile_peak"].append(st.peak_tile_rows)
                rec[m]["row_peak"].append(st.peak_rows)
                rec[m]["a2a"].append(st.total_a2a_bytes)
        rep = execs["alg6_rep"]
        out[shape] = {
            "steps": steps,
            "matches_single_device": worst <= EP_TOL_EPS,
            "max_mismatch_eps": worst,
            "per_method": {m: {k: [int(v) for v in vs]
                               for k, vs in r.items()}
                           for m, r in rec.items()},
            "rebalances": rep.rebalances,
            "rebalances_skipped": rep.rebalances_skipped,
            "replication_factor": float(rep.placement.replication_factor),
            "max_rows": int(execs["off"]._resolve_max_rows(
                None, None, None, tr["x"].shape[1] // S * K)),
        }
    return out


def _ratios(shape_rec: dict) -> dict:
    pm = shape_rec["per_method"]
    off_t = np.asarray(pm["off"]["tile_peak"], float)
    off_r = np.asarray(pm["off"]["row_peak"], float)
    res = {}
    for m in ("alg6", "alg6_rep"):
        mt = np.maximum(np.asarray(pm[m]["tile_peak"], float), 1.0)
        res[f"peak_rows_ratio_{m}"] = float((off_t / mt).mean())
        res[f"peak_rows_ratio_{m}_min"] = float((off_t / mt).min())
        res[f"peak_real_rows_ratio_{m}"] = float(
            (off_r / np.maximum(np.asarray(pm[m]["row_peak"], float),
                                1.0)).mean())
    res["a2a_bytes_baseline"] = int(np.mean(pm["off"]["a2a"]))
    res["a2a_bytes_xshare"] = int(np.mean(pm["alg6_rep"]["a2a"]))
    return res


def run(quick: bool = False) -> dict:
    from benchmarks.common import (DATASETS, eval_tokens,
                                   teacher_forced_decode_ce,
                                   trained_model)
    from repro.configs.base import XSharePolicy

    import jax
    if len(jax.devices()) < G:
        raise RuntimeError(
            f"table2_ep measures an {G}-shard EP mesh but this process "
            f"sees {len(jax.devices())} device(s); on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={G} before starting")
    cfg, params, fam, _ = trained_model(E, K, steps=60 if quick else 150)
    rows = []
    claims = {}
    if not quick:
        # the original simulated Table 2 (paper metric: activated
        # experts + peak per-group load + CE proxy)
        for bs in (8, 16):
            toks = eval_tokens(fam, DATASETS, batch_per=bs // 4, seq=40)
            base = teacher_forced_decode_ce(
                cfg, params, toks, XSharePolicy(mode="off", num_groups=G))
            alg6 = teacher_forced_decode_ce(
                cfg, params, toks,
                XSharePolicy(mode="ep", k0=1, m_g=5, num_groups=G))
            padded_rows_per_shard = (E // G) * bs
            rows.append({"batch": bs, "method": "baseline", **base})
            rows.append({"batch": bs, "method": "alg6(1,5)", **alg6})
            claims[f"bs{bs}"] = {
                "experts_drop": 1 - alg6["activated"] / base["activated"],
                "peak_load_ratio": base["max_load"]
                / max(alg6["max_load"], 1e-9),
                "peak_shard_tokens_ratio":
                    base["max_shard_tokens"]
                    / max(alg6["max_shard_tokens"], 1e-9),
                "real_vs_padded_shard_rows":
                    alg6["max_shard_tokens"] / padded_rows_per_shard,
                "ce_delta": alg6["ce"] - base["ce"],
                "max_load_bound_ok": alg6["max_load"] <= 5 + 1e-6,
            }

    # ---- measured EP execution (G shards, one per device group) -----
    shards = G
    bs = 16
    steps = 4 if quick else 10
    traces = _routing_traces(cfg, params, fam, bs=bs, steps=steps,
                             shards=shards)
    measured = _measure(params, traces, shards)
    dec, spec = measured["dec"], measured["spec"]
    ep = {
        "batch": bs,
        "steps": dec["steps"],
        "shards": shards,
        "block_t": BLOCK_T,
        "matches_single_device":
            dec["matches_single_device"] and
            spec["matches_single_device"],
        # headline: Algorithm 6 + replication vs baseline routing,
        # measured peak-shard executed rows at decode, mean over steps
        "peak_rows_ratio": _ratios(dec)["peak_rows_ratio_alg6_rep"],
        **_ratios(dec),
        "replication_factor": dec["replication_factor"],
        "rebalances": dec["rebalances"],
        "rebalances_skipped": dec["rebalances_skipped"],
        "spec_shape": [bs, 1 + SPEC_LS],
        "spec_peak_rows_ratio":
            _ratios(spec)["peak_rows_ratio_alg6_rep"],
        "spec_matches_single_device": spec["matches_single_device"],
        "spec_a2a_bytes_xshare": _ratios(spec)["a2a_bytes_xshare"],
    }
    with open(BENCH_PATH, "w") as f:
        json.dump({"ep": ep, "measured_detail": measured}, f, indent=1,
                  default=float)
    claims["ep_measured"] = ep
    if quick:
        claims["bs16"] = {"quick": True, **ep}
    return {"rows": rows, **claims}


if __name__ == "__main__":
    print(json.dumps(run(quick="--quick" in sys.argv), indent=1,
                     default=float))
