"""Serving step factories (used by the dry-run) + runnable CLI demo.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --reduced --batch 4 --prompt-len 32 --new-tokens 32 \
        --policy batch --m-l 8 --k0 1
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig, XSharePolicy
from repro.models import decode_step, prefill
from repro.models.moe import OFF


def make_prefill(cfg: ArchConfig, *, cache_len: int,
                 force_window: Optional[int] = None,
                 capacity_factor: float = 2.0):
    def fn(params, tokens, prefix_embeds=None):
        return prefill(cfg, params, tokens, cache_len=cache_len,
                       prefix_embeds=prefix_embeds,
                       force_window=force_window,
                       capacity_factor=capacity_factor)
    return fn


def make_serve_step(cfg: ArchConfig, *, policy: XSharePolicy = OFF,
                    force_window: Optional[int] = None,
                    capacity_factor: float = 2.0):
    """One decode step: T=1 new token against the cache."""
    def fn(params, tokens, cache):
        return decode_step(cfg, params, tokens, cache, policy=policy,
                           force_window=force_window,
                           capacity_factor=capacity_factor)
    return fn


def main(argv=None) -> None:
    import numpy as np
    from repro.compile_cache import enable_compile_cache
    from repro.configs.registry import get_config
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.serving import Engine

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--policy", default="off",
                    choices=["off", "batch", "spec", "ep"])
    ap.add_argument("--k0", type=int, default=1)
    ap.add_argument("--m-l", type=int, default=8)
    ap.add_argument("--m-r", type=int, default=4)
    ap.add_argument("--m-g", type=int, default=4)
    ap.add_argument("--spec-len", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    policy = XSharePolicy(mode=args.policy, k0=args.k0, m_l=args.m_l,
                          m_r=args.m_r, m_g=args.m_g)
    lm = SyntheticLM(cfg.vocab_size, name=args.arch)
    rng = np.random.default_rng(args.seed)
    prompts = lm.sample(rng, args.batch, args.prompt_len)

    draft = None
    if args.spec_len:
        # the draft proposes target tokens: it keeps the whole vocabulary
        dcfg = cfg.reduced(num_layers=2, max_d_model=128,
                           max_vocab=cfg.vocab_size)
        draft = (dcfg, init_params(dcfg, jax.random.PRNGKey(1)))

    eng = Engine(cfg, params, policy=policy,
                 cache_len=args.prompt_len + args.new_tokens + 16,
                 draft=draft, spec_len=args.spec_len)
    toks, stats = eng.generate(prompts, args.new_tokens)
    print("generated:", toks.shape)
    print(f"OTPS {stats.otps:.1f}  steps {stats.steps}")
    if stats.accepted_hist:
        print(f"mean accepted drafts/step: {stats.mean_accepted:.2f}")
    if stats.layer_aux:
        print(f"mean activated experts/layer: "
              f"{stats.mean_aux('activated_experts'):.2f} "
              f"(selected set {stats.mean_aux('selected_set'):.2f}, "
              f"gate mass {stats.mean_aux('gate_mass'):.3f})")


if __name__ == "__main__":
    main()
