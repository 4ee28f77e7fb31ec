"""The served path names its own layers.

Device work carries ``jax.named_scope`` names in each instruction's
``op_name`` metadata (the compiled code is otherwise unchanged); the
scheduler's host phases are ``jax.profiler.TraceAnnotation`` spans
whose keyword args are their counters. Both are read back here on the
CPU at a tiny size: the scopes from the fused decode program the
scheduler compiles, the spans from a profile of a short run.
"""
import glob
import json
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import XSharePolicy
from repro.configs.registry import ARCHS
from repro.models import init_params
from repro.serving import Scheduler
from repro.serving.step import NO_FAULT

SCOPES = ["attention", "attention/kv_write", "moe", "moe/route",
          "moe/dispatch", "moe/experts", "moe/combine", "lm_head", "sample"]
POLICY = XSharePolicy(mode="batch", k0=1, m_l=2)


@pytest.fixture(scope="module")
def setup():
    cfg = ARCHS["granite-moe-1b-a400m"].reduced(num_layers=2, max_d_model=128,
                                                 max_vocab=256)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (3, 8), 0, cfg.vocab_size))
    return cfg, params, prompts


def scheduler(cfg, params, **kw):
    return Scheduler(cfg, params, num_slots=2, cache_len=32, policy=POLICY,
                     admission="affinity", dispatch="sorted", decode_chunk=4,
                     **kw)


@pytest.fixture(scope="module")
def fused_op_names(setup):
    """The op_name metadata of every instruction of the compiled fused
    decode scan (XShare batch policy, sorted dispatch)."""
    cfg, params, _ = setup
    s = scheduler(cfg, params)
    n = s.num_slots
    text = s.fns.fused.lower(
        params, s._tok, s._cache, np.zeros(n, np.int32),
        jax.random.PRNGKey(0), np.asarray(NO_FAULT, np.int32)
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
def test_fused_decode_program_names_scope(fused_op_names, scope):
    assert any(f"/{scope}/" in f"/{n}" for n in fused_op_names), scope


def serve(cfg, params, prompts):
    s = scheduler(cfg, params)
    for p in prompts:
        s.submit(p, 9)
    return [np.asarray(st.tokens) for st in s.run()]


def host_spans(logdir):
    """(name, start_ns, stats) of the profile's ``serve/`` spans, in
    start order."""
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, dict(ev.stats))
                        for ev in line.events
                        if ev.name.startswith("serve/")]
    return sorted(out, key=lambda s: s[1])


def test_scheduler_spans_and_counters(setup, tmp_path):
    cfg, params, prompts = setup
    serve(cfg, params, prompts)                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        traced = serve(cfg, params, prompts)
    spans = host_spans(tmp_path)
    rounds = [(n, st["round"]) for n, _, st in spans
              if n in ("serve/decode", "serve/harvest")]
    assert rounds, "no decode round was recorded"
    # each round is its decode span, then its harvest span
    assert rounds == [(n, r) for r in range(len(rounds) // 2)
                      for n in ("serve/decode", "serve/harvest")]
    harvest = [st for n, _, st in spans if n == "serve/harvest"]
    assert sum(st["tokens"] for st in harvest) == \
        sum(len(t) - 1 for t in traced)          # the first is prefilled
    assert sum(st["finished"] for st in harvest) == len(prompts)
    # two slots: two requests share the first prefill, the third waits
    # for a free slot; the span's rids name them
    prefills = [st for n, _, st in spans if n == "serve/prefill"]
    rids = [json.loads(p["rids"]) for p in prefills]
    assert [len(r) for r in rids] == [p["group"] for p in prefills] == [2, 1]
    assert sorted(sum(rids, [])) == [0, 1, 2]
    assert all(p["prompt_len"] == 8 for p in prefills)
    probes = [st for n, _, st in spans if n == "serve/probe"]
    assert [(p["rid"], p["prompt_len"]) for p in probes] == \
        [(0, 8), (1, 8), (2, 8)]
    admits = [st for n, _, st in spans if n == "serve/admit"]
    assert sum(st["admitted"] for st in admits) == len(prompts)


def test_cache_in_place_counters(setup, tmp_path):
    """Every fused decode round and every insert of an admitted request
    updates the running cache in place: ``serve/decode`` spans read
    ``cache_in_place`` 1, ``serve/prefill`` spans count as many inserts
    in place as inserts, and the scheduler's totals agree."""
    cfg, params, prompts = setup
    serve(cfg, params, prompts)                  # compile outside the trace
    s = scheduler(cfg, params)
    for p in prompts:
        s.submit(p, 9)
    with jax.profiler.trace(str(tmp_path)):
        s.run()
    spans = host_spans(tmp_path)
    decodes = [st["cache_in_place"] for n, _, st in spans
               if n == "serve/decode"]
    assert decodes and decodes == [1] * len(decodes)
    prefills = [(st["inserts"], st["inserts_in_place"]) for n, _, st in spans
                if n == "serve/prefill"]
    # the first group fills the empty machine whole; the third request
    # is spliced into the slot the first to finish leaves
    assert prefills == [(0, 0), (1, 1)]
    assert (s.decodes_in_place, s.inserts_in_place) == (len(decodes), 1)


def test_profiler_leaves_tokens_unchanged(setup, tmp_path):
    cfg, params, prompts = setup
    plain = serve(cfg, params, prompts)
    with jax.profiler.trace(str(tmp_path)):
        traced = serve(cfg, params, prompts)
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert np.array_equal(a, b)
