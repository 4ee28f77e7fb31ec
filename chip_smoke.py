"""Chip smoke test: the served XShare path on a TPU at the published widths
of Granite-3.0-1B-A400M (ibm-granite/granite-3.0-1b-a400m-base: 24
layers, d_model 1024, 32 experts top-8, vocab 49155), weights drawn from
``--seed`` in bf16.

    python chip_smoke.py             # one chip: the serving phases
    python chip_smoke.py --chips 4   # four chips: the expert-parallel phase

One chip runs, in order: the device check; continuous batching through
``Engine.make_scheduler`` (16 requests, three prompt lengths, staggered
arrivals) checked against the lockstep reference; the Pallas grouped
GEMM present in the scheduler's prefill program; and the speculative
scheduler checked against plain greedy decode. ``--chips 4`` runs only
the expert-parallel phase: the shard_map EP layer at decode, spec-verify
and prefill shapes, and a whole-model EP prefill, each against one chip.

Lines before the last are informational (compile seconds and counts,
peak device memory, activated experts, wall tokens/s). The last line of
stdout is one JSON object, printed only when every check passed. Any
failed check exits non-zero. So does a missing TPU: the script never
falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-moe-1b-a400m"
CACHE_LEN = 1024
NEW_TOKENS = 32
NUM_SLOTS = 8
SPEC_LEN = 3
# Whole-model EP prefill vs one chip: ep.ep_mismatch over the logits of
# the real vocabulary (the padded columns hold -1e30 and would swamp any
# scale) and over every layer's KV cache, which carries each position's
# hidden state where the logits hold only the last one. On a 4-layer,
# 32-expert, d_model-256 cut of this model in bf16 (CPU, four prompt
# sets) sound runs read at most 3.56 (0.37 on the logits alone); two
# experts swapped on one shard read 17.3 or more (1.7 to 12 on the
# logits alone). At full width on four TPU v5e chips a sound run read
# 5.05 and the planted swap 38.4, both on the KV cache. The chip run
# plants that swap and requires it to fail.
WHOLE_MODEL_TOL_EPS = 8.0


def require_tpu():
    """Exit non-zero unless JAX's default device is a TPU; return the
    device list. Prints platform, kind, count and JAX version."""
    import jax
    devices = jax.devices()
    d = devices[0]
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}")
    return devices


class CompileMeter:
    """Counts XLA compiles and their seconds through jax.monitoring. A
    persistent-cache hit counts as a hit, not a compile; ``long`` counts
    the compiles of a second or more (whole-model programs, where the
    rest are the small eager ops) and ``long_names`` names them: a
    program of that length that compiled did not come from the cache."""

    def __init__(self):
        import jax
        self.compiles, self.hits, self.long, self.seconds = 0, 0, 0, 0.0
        self.long_names = []
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            if secs >= 1.0:
                self.long += 1
                self.long_names.append(f"{fun_name}:{secs:.1f}s")

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.compiles - self.hits, self.long, self.hits, self.seconds

    def report(self, phase, since):
        now = self.mark()
        c, lg, h, s = (a - b for a, b in zip(now, since))
        print(f"[{phase}] compiles={c} of_which_over_1s={lg} "
              f"cache_hits={h} compile_s={s:.1f} over_1s="
              f"{self.long_names[since[1]:now[1]]}", flush=True)


class Checks:
    """Collects failed checks so one run reports all of them."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what):
        print(f"{'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def build_model(seed):
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.models import init_params, param_count
    cfg = get_config(ARCH)
    params = jax.jit(lambda k: init_params(cfg, k, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    print(f"model {cfg.name}: layers={cfg.num_layers} d={cfg.d_model} "
          f"experts={cfg.moe.num_experts} top_k={cfg.moe.top_k} "
          f"vocab={cfg.vocab_size} params={param_count(params)} bf16",
          flush=True)
    return cfg, params


def draft_config(cfg):
    """A small dense draft over the target's whole vocabulary."""
    from repro.configs.base import ArchConfig, AttnConfig
    return ArchConfig(name="chip-smoke-draft", family="dense", num_layers=2,
                      d_model=256, d_ff=512, vocab_size=cfg.vocab_size,
                      attn=AttnConfig(num_heads=4, num_kv_heads=4,
                                      head_dim=64),
                      tie_embeddings=True)


def smoke_prompts(cfg, seed):
    """16 requests: 8 of 128 tokens at t=0, then 4 + 4 of 256 and 512
    tokens arriving at 1 s and 2 s. The t=0 wave shares one prompt
    length, so its rows form one running batch from first to last token
    — the batch the lockstep reference decodes."""
    import numpy as np
    from repro.data import SyntheticLM
    lm = SyntheticLM(cfg.vocab_size, name="chip-smoke")
    rng = np.random.default_rng(seed)
    wave0 = lm.sample(rng, NUM_SLOTS, 128)
    reqs = [(p, 0.0) for p in wave0]
    for t in (1.0, 2.0):
        for n in (256, 512):
            reqs += [(p, t) for p in lm.sample(rng, 2, n)]
    return wave0, reqs


def first_mismatch(a, b):
    """(row, position) of the first differing token, or None."""
    import numpy as np
    diff = np.argwhere(np.asarray(a) != np.asarray(b))
    return None if not len(diff) else tuple(int(v) for v in diff[0])


def serve_phase(cfg, params, seed, check, meter):
    """Continuous batching through Engine.make_scheduler, the lockstep
    reference, and the compiled Pallas kernel in the prefill program."""
    import numpy as np
    from repro.configs.base import XSharePolicy
    from repro.serving import Engine
    from repro.serving.errors import REASON_COMPLETED

    since = meter.mark()
    eng = Engine(cfg, params, policy=XSharePolicy(mode="batch", k0=1, m_l=8),
                 cache_len=CACHE_LEN)
    wave0, reqs = smoke_prompts(cfg, seed)
    for attempt in ("cold", "warm"):
        sched = eng.make_scheduler(num_slots=NUM_SLOTS, admission="affinity")
        states = [sched.submit(p, NEW_TOKENS, arrival_s=t) for p, t in reqs]
        mark = meter.mark()
        sched.run(max_wall_s=600)
        toks = sum(len(st.tokens) for st in states)
        print(f"[serve {attempt}] requests={len(states)} tokens={toks} "
              f"wall_s={sched.wall_s:.2f} tokens_per_s="
              f"{toks / sched.wall_s:.1f} compiles_over_1s_in_run="
              f"{meter.mark()[1] - mark[1]}", flush=True)
        done = [st.finish_reason == REASON_COMPLETED
                and len(st.tokens) == NEW_TOKENS for st in states]
        check(all(done), f"{attempt}: all {len(states)} requests completed "
              f"with {NEW_TOKENS} tokens ({sched.reason_counts()})")
        flat = np.concatenate([np.asarray(st.tokens) for st in states])
        check(bool(((flat >= 0) & (flat < cfg.vocab_size)).all()),
              f"{attempt}: every token id in [0, {cfg.vocab_size})")
    act = np.stack([np.asarray(a["activated_experts"])
                    for a in sched.step_aux])             # (steps, layers)
    print(f"[serve] activated experts per layer: mean {act.mean():.2f} of "
          f"{cfg.moe.num_experts} (per layer "
          f"{np.round(act.mean(0), 2).tolist()})", flush=True)

    # decode logits are checked in the fused scan (a non-finite row is
    # quarantined, so it would not have completed); prefill's here
    lg, _, _ = sched.fns.prefill(params, wave0)
    check(bool(np.isfinite(np.asarray(lg, np.float32)).all()),
          f"prefill logits finite {tuple(lg.shape)}")

    served = np.stack([np.asarray(st.tokens) for st in states[:NUM_SLOTS]])
    ref, _ = eng.generate(wave0, NEW_TOKENS, lockstep=True)
    mm = first_mismatch(served, ref)
    check(mm is None, f"served tokens == lockstep reference for the t=0 "
          f"wave {served.shape} (first mismatch row,pos: {mm})")

    hlo = sched.fns.prefill.lower(params, wave0).as_text()
    check("tpu_custom_call" in hlo,
          f"scheduler prefill {wave0.shape} lowers the Pallas grouped_ffn "
          f"(tpu_custom_call)")
    meter.report("serve", since)
    return wave0


def spec_phase(cfg, params, prompts, seed, check, meter):
    """SpecScheduler with a dense draft, spec and plain requests in one
    batch; greedy output must equal plain greedy decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import init_params
    from repro.serving import Engine, SpecConfig
    from repro.serving.errors import REASON_COMPLETED

    since = meter.mark()
    dcfg = draft_config(cfg)
    dparams = init_params(dcfg, jax.random.PRNGKey(seed + 1), jnp.bfloat16)
    eng = Engine(cfg, params, cache_len=CACHE_LEN, draft=(dcfg, dparams),
                 spec_len=SPEC_LEN)
    sched = eng.make_scheduler(
        num_slots=NUM_SLOTS,
        spec_cfg=SpecConfig(spec_len=SPEC_LEN, num_rounds=4))
    states = [sched.submit(p, NEW_TOKENS, spec=(i % 2 == 0))
              for i, p in enumerate(prompts)]
    sched.run(max_wall_s=600)
    drafted = sum(st.drafted for st in states)
    accepted = sum(st.accepted_drafts for st in states)
    toks = sum(len(st.tokens) for st in states)
    print(f"[spec] requests={len(states)} spec={sum(s.req.spec for s in states)} "
          f"tokens={toks} wall_s={sched.wall_s:.2f} tokens_per_s="
          f"{toks / sched.wall_s:.1f} drafted={drafted} accepted={accepted}",
          flush=True)
    check(all(st.finish_reason == REASON_COMPLETED
              and len(st.tokens) == NEW_TOKENS for st in states),
          f"spec: all {len(states)} requests completed")
    served = np.stack([np.asarray(st.tokens) for st in states])
    plain, _ = Engine(cfg, params, cache_len=CACHE_LEN).generate(
        prompts, NEW_TOKENS, lockstep=True)
    mm = first_mismatch(served, plain)
    check(mm is None, f"spec scheduler tokens == plain greedy decode "
          f"{served.shape} (first mismatch row,pos: {mm})")
    meter.report("spec", since)


def ep_phase(cfg, params, seed, check, meter, shards=4):
    """The shard_map EP layer and a whole-model EP prefill on `shards`
    chips, each compared with one chip in this process. Each comparison
    is also run on a planted fault, which must fail it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import ep as EP
    from repro.configs.base import EPConfig, XSharePolicy
    from repro.data import SyntheticLM
    from repro.models import embed_tokens, prefill
    from repro.models.dispatch import sorted_expert_ffn
    from repro.models.moe import route
    from repro.sharding import make_ep_mesh

    since = meter.mark()
    mesh = make_ep_mesh(shards)
    moe = cfg.moe
    layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    w1, w3, w2 = layer["w1"], layer["w3"], layer["w2"]
    lm = SyntheticLM(cfg.vocab_size, name="chip-smoke-ep")
    rng = np.random.default_rng(seed)
    alg6 = XSharePolicy(mode="ep", k0=1, m_g=2, num_groups=shards)
    contiguous = EP.EPExecutor.from_config(EPConfig(num_shards=shards),
                                           moe.num_experts, mesh=mesh)
    for name, (b, t) in (("decode", (16, 1)), ("verify", (16, 1 + SPEC_LEN)),
                         ("prefill", (1, 2048))):
        toks = jnp.asarray(lm.sample(rng, b, max(t, 8))[:, :t])
        x = embed_tokens(cfg, params, toks).reshape(b * t, cfg.d_model)
        for method, policy in (("baseline", XSharePolicy(mode="off")),
                               ("alg6+lpt+rep", alg6)):
            idx, w, _, _ = route(layer, x, moe, policy)
            if method == "baseline":
                ex = contiguous
            else:
                load = np.zeros(moe.num_experts)
                np.add.at(load, np.asarray(idx).reshape(-1).clip(0),
                          np.asarray(w).reshape(-1) != 0)
                ex = EP.EPExecutor.from_config(
                    EPConfig(num_shards=shards, replicate_hot=1,
                             max_replicas=2),
                    moe.num_experts, mesh=mesh, load=load)
            y, st = ex(x, w1, w3, w2, idx, w)
            ref = sorted_expert_ffn(x, w1, w3, w2, idx, w)
            err = EP.ep_mismatch(y, ref)
            # planted fault: the batch's smallest-gate row dropped, the
            # least visible lost row (same shapes, so no new compile)
            wf = np.asarray(w, np.float32)
            lost = np.unravel_index(int(np.argmin(np.where(
                wf > 0, wf, np.inf))), wf.shape)
            y_bad, _ = ex(x, w1, w3, w2, idx, w.at[lost].set(0))
            err_bad = EP.ep_mismatch(y_bad, ref)
            print(f"[ep {name} {b}x{t} {method}] rows/shard="
                  f"{st.computed_rows.tolist()} tile_rows/shard="
                  f"{st.tile_rows.tolist()} a2a_bytes/shard="
                  f"{st.a2a_bytes.tolist()} max_rows={st.max_rows}",
                  flush=True)
            check(err <= EP.EP_TOL_EPS,
                  f"ep {name} {method}: mismatch {err:.3f} <= "
                  f"{EP.EP_TOL_EPS} eps")
            check(err_bad > EP.EP_TOL_EPS,
                  f"ep {name} {method}: planted fault (row of gate "
                  f"{wf[lost]:.4f} dropped) mismatch {err_bad:.3f} > "
                  f"{EP.EP_TOL_EPS} eps")

    toks = jnp.asarray(lm.sample(rng, 2, 256))
    V = cfg.vocab_size

    def outputs(fn):
        lg, cache, _ = fn(params, toks)
        return {"logits": np.asarray(lg[..., :V]),
                "kv_k": np.asarray(cache["kv_k"]),
                "kv_v": np.asarray(cache["kv_v"])}

    def ep_outputs(executor):
        fn = jax.jit(lambda p, t: prefill(cfg, p, t, cache_len=CACHE_LEN,
                                          dispatch="ep"))
        with EP.ep_context(executor):
            return fn.lower(params, toks).as_text(), outputs(fn)

    def mismatch(out):
        return {k: round(EP.ep_mismatch(v, one[k]), 3)
                for k, v in out.items()}

    one = outputs(jax.jit(lambda p, t: prefill(
        cfg, p, t, cache_len=CACHE_LEN, dispatch="sorted")))
    hlo, out = ep_outputs(contiguous)
    # the program must carry the exchange and the compiled Pallas
    # grouped_ffn inside the shard_map (the only Pallas kernel prefill
    # runs), not the tile-gather fallback
    check("all_to_all" in hlo, "whole-model EP prefill lowers an all_to_all")
    check("tpu_custom_call" in hlo, "whole-model EP prefill lowers the "
          "Pallas grouped_ffn (tpu_custom_call)")
    err = mismatch(out)
    finite = all(np.isfinite(np.asarray(v, np.float32)).all()
                 for v in out.values())
    argmax = (out["logits"].argmax(-1) == one["logits"].argmax(-1)).tolist()
    check(finite and max(err.values()) <= WHOLE_MODEL_TOL_EPS,
          f"whole-model EP prefill {tuple(toks.shape)} vs one chip: "
          f"mismatch {err} <= {WHOLE_MODEL_TOL_EPS} eps; argmax agree "
          f"{argmax}")
    # planted fault: experts of slots 0 and 1 on shard 0 swapped, so
    # their rows run through each other's weights in every layer
    pl = contiguous.placement
    eids = pl.local_eids.copy()
    eids[0, [0, 1]] = eids[0, [1, 0]]
    swapped = EP.EPExecutor(mesh, dataclasses.replace(pl, local_eids=eids))
    err_bad = mismatch(ep_outputs(swapped)[1])
    check(max(err_bad.values()) > WHOLE_MODEL_TOL_EPS,
          f"whole-model EP prefill, planted fault (experts {eids[0, 1]} "
          f"and {eids[0, 0]} swapped on shard 0): mismatch {err_bad} > "
          f"{WHOLE_MODEL_TOL_EPS} eps")
    meter.report("ep", since)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} devices")
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    check = Checks()
    t0 = time.perf_counter()
    mark = meter.mark()
    cfg, params = build_model(args.seed)
    meter.report("model", mark)
    if args.chips == 1:
        prompts = serve_phase(cfg, params, args.seed, check, meter)
        spec_phase(cfg, params, prompts, args.seed, check, meter)
    else:
        ep_phase(cfg, params, args.seed, check, meter, shards=args.chips)
    meter.report("total", (0, 0, 0, 0.0))
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
