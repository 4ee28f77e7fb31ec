"""The harness without its look for a chip, driving the rest of a run at
a tiny size on the CPU: a sound run is correct, and a run whose timed
path alters a token where it is produced, or whose decode step hands
back its cache unchanged, is not."""
import numpy as np
import pytest

import tiny


def alter_token(call_index=2):
    """Break the timed path: the fused decode call number `call_index`
    hands the scheduler a different token for its first live slot."""
    def fault(engine):
        fns = engine._fns
        calls = [0]

        def fused(p, tok, cache, rem, key, f):
            out = fns.fused(p, tok, cache, rem, key, f)
            calls[0] += 1
            if calls[0] - 1 != call_index:
                return out
            toks = np.array(out[2])
            slot = int(np.flatnonzero(np.asarray(out[4])[0])[0])
            toks[0, slot] = (toks[0, slot] + 250) % 500
            return out[:2] + (toks,) + out[3:]
        import dataclasses
        engine._fns = dataclasses.replace(fns, fused=fused)
    return fault


def stale_cache(from_call=2):
    """Break the timed path: from fused call `from_call` on, the call
    returns the cache it was given, so the keys and values it wrote are
    lost (a step that returns its state unchanged). The call donates the
    cache it is given, so what it hands back is a copy taken before."""
    def fault(engine):
        import jax
        import jax.numpy as jnp
        fns = engine._fns
        calls = [0]

        def fused(p, tok, cache, rem, key, f):
            calls[0] += 1
            if calls[0] - 1 < from_call:
                return fns.fused(p, tok, cache, rem, key, f)
            before = jax.tree.map(jnp.copy, cache)
            out = fns.fused(p, tok, cache, rem, key, f)
            return out[:1] + (before,) + out[2:]
        import dataclasses
        engine._fns = dataclasses.replace(fns, fused=fused)
    return fault


@pytest.mark.parametrize("mix", ["closed", "open"])
def test_sound_run_is_correct(mix):
    run = tiny.run(tiny.CLOSED if mix == "closed" else tiny.OPEN,
                   {"logit_gap_max": tiny.GAP_LIMIT, "tokens_checked_min": 30})
    assert run.correct, run.compared


@pytest.mark.parametrize("fault", [alter_token, stale_cache])
@pytest.mark.parametrize("mix", ["closed", "open"])
def test_broken_path_is_not_correct(mix, fault):
    run = tiny.run(tiny.CLOSED if mix == "closed" else tiny.OPEN,
                   {"logit_gap_max": tiny.GAP_LIMIT, "tokens_checked_min": 30},
                   fault=fault())
    assert not run.correct
    assert run.compared["logit_gap_max"]["value"] > tiny.GAP_LIMIT
