"""Drives the served path from its outer entry: the engine's front door
(no journal) over the scheduler, the fused decode scan and the model.

The harness wraps the callables of the engine's compiled bundle
(``Engine._fns``: prefill, insert, fused, probe) before the front door
is built. The wrappers record what each call was asked to do, which the
reference replays, and, in a traced run, put a profiler span around
each call. Nothing of the program is changed.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

SPAN_NAMES = {"prefill": "prefill", "insert": "insert",
              "fused": "fused_decode", "probe": "submit"}


class Recorder:
    """Wraps a StepFns bundle. ``events`` holds, in call order,
    ("prefill", prompts, t0, t1, activated_experts, aux),
    ("insert", slot, row, t0, t1) and
    ("fused", remaining, outputs, t0, t1, aux); `aux` is every counter
    the call returned, by the program's own key (per layer; per step
    and layer in a fused call). Device arrays stay on the device until
    ``host()`` is called after the window."""

    def __init__(self, fns, *, traced: bool):
        import jax
        missing = [n for n in SPAN_NAMES if not callable(getattr(fns, n,
                                                                 None))]
        if missing or not hasattr(fns, "decode_chunk"):
            raise RuntimeError(
                f"the engine's StepFns bundle lacks {missing or 'decode_chunk'}"
                ": the harness cannot record the served path")
        self._jax = jax
        self.traced = traced
        self.events: List[tuple] = []
        self.orig = fns
        self.fns = dataclasses.replace(
            fns, prefill=self._prefill, insert=self._insert,
            fused=self._fused, probe=self._probe)

    def _call(self, name, fn, args):
        """In a traced run the span carries the index the call's event
        will have, and the call is waited for inside it, so the device
        operations of the call fall inside its span."""
        jax = self._jax
        t0 = time.perf_counter()
        if self.traced:
            with jax.profiler.TraceAnnotation(SPAN_NAMES[name],
                                              call=len(self.events)):
                out = fn(*args)
                jax.block_until_ready(out)
        else:
            out = fn(*args)
        return out, t0, time.perf_counter()

    def _prefill(self, p, tokens):
        out, t0, t1 = self._call("prefill", self.orig.prefill, (p, tokens))
        self.events.append(("prefill", np.asarray(tokens), t0, t1,
                            out[2].get("activated_experts"), out[2]))
        return out

    def _insert(self, cache, req_cache, slot, src):
        out, t0, t1 = self._call("insert", self.orig.insert,
                                 (cache, req_cache, slot, src))
        self.events.append(("insert", slot, src, t0, t1))
        return out

    def _fused(self, p, tok, cache, rem, key, fault):
        out, t0, t1 = self._call("fused", self.orig.fused,
                                 (p, tok, cache, rem, key, fault))
        _, _, toks, aux, ok, poisoned = out
        self.events.append(("fused", rem, (toks, ok, poisoned,
                                           aux.get("activated_experts")),
                            t0, t1, aux))
        return out

    def _probe(self, p, tokens):
        out, _, _ = self._call("probe", self.orig.probe, (p, tokens))
        return out

    def host(self) -> List[tuple]:
        """The events with every device array brought to the host."""
        def fetch(aux):
            return self._jax.tree.map(np.asarray, aux)
        out = []
        for ev in self.events:
            if ev[0] == "prefill":
                act = None if ev[4] is None else np.asarray(ev[4])
                out.append(("prefill", ev[1], ev[2], ev[3], act,
                            fetch(ev[5])))
            elif ev[0] == "insert":
                out.append(("insert", int(ev[1]), int(ev[2]), ev[3], ev[4]))
            else:
                toks, ok, poisoned, act = ev[2]
                out.append(("fused", np.asarray(ev[1]),
                            (np.asarray(toks), np.asarray(ok),
                             np.asarray(poisoned),
                             None if act is None else np.asarray(act)),
                            ev[3], ev[4], fetch(ev[5])))
        return out


@dataclasses.dataclass
class Client:
    """What one request looked like from the client's side."""
    req: object
    stream: object = None
    due: float = 0.0          # when it was due to be sent (perf_counter)
    sent: float = 0.0         # when it was sent
    first: float = float("nan")
    done: float = float("nan")
    seen: int = 0
    reason: Optional[str] = None


def serve(engine, mix: Dict, reqs: List, seconds: float, *,
          traced: bool, on_window=None, poll_s: float = 0.005):
    """Serve `reqs` through the front door for one window.

    Closed loop: the first ``clients`` requests are sent before the
    door opens, and the window opens when the first fused decode call
    starts, i.e. once the first wave fills the slots. Open loop: the
    door opens empty and each request is sent when due; the window
    opens ``lead_in_s`` later. After the window no request is sent. In
    the open loop the harness waits (at most 60 s) for every request
    sent in the window to see its first token, which its TTFT needs;
    then it cancels what is left and drains the door. Clients are polled
    every `poll_s` (tokens are published once per fused call), and the
    sender wakes when the next request is due.

    ``on_window(t_open)`` is called from this thread as the window
    opens (the traced run starts the profiler there). Returns a dict of
    host-clock readings and the recorder.
    """
    import jax
    from repro.serving.frontdoor import FrontDoor

    rec = Recorder(engine._fns, traced=traced)
    engine._fns = rec.fns
    door = FrontDoor(engine, num_slots=mix["slots"],
                     admission=mix["admission"])
    clients: List[Client] = []
    tokens_in_window = 0

    def send(req, due):
        c = Client(req=req, due=due, sent=time.perf_counter())
        if traced:
            with jax.profiler.TraceAnnotation("generator"):
                c.stream = door.submit(req.prompt, req.max_new)
        else:
            c.stream = door.submit(req.prompt, req.max_new)
        clients.append(c)
        return c

    closed = mix["loop"] == "closed"
    pending = list(reqs)
    # what set-up made is never garbage: keep the collector's passes in
    # the window short, so that they stall no serving thread
    gc.collect()
    gc.freeze()
    if closed:
        t_start = time.perf_counter()
        for _ in range(mix["clients"]):
            send(pending.pop(0), t_start)
        door.start()
        while not any(ev[0] == "fused" for ev in rec.events):
            if door.crashed is not None:
                raise RuntimeError(f"front door crashed: {door.crashed!r}")
            time.sleep(poll_s)
        t_open = next(ev[3] for ev in rec.events if ev[0] == "fused")
    else:
        door.start()
        t_start = time.perf_counter()
        t_open = t_start + mix["lead_in_s"]
        pending = [(t_start + r.due_s, r) for r in reqs]
    t_close = t_open + seconds
    window_started = False
    live: List[Client] = list(clients)
    while True:
        now = time.perf_counter()
        if not window_started and now >= t_open:
            window_started = True
            if on_window is not None:
                on_window(t_open)
        if not closed:
            while pending and pending[0][0] <= now and now < t_close:
                due, req = pending.pop(0)
                live.append(send(req, due))
        still = []
        for c in live:
            done = c.stream.done      # read first: tokens land before it
            n = len(c.stream.tokens)
            if n > c.seen:
                if c.seen == 0:
                    c.first = now
                if t_open <= now < t_close:
                    tokens_in_window += n - c.seen
                c.seen = n
            if done:
                c.done = now
                c.reason = c.stream.finish_reason or repr(c.stream.error)
                if closed and now < t_close and pending:
                    still.append(send(pending.pop(0), now))
            else:
                still.append(c)
        live = still
        if closed and not pending and now < t_close:
            raise RuntimeError("the closed loop ran out of requests")
        if now >= t_close:
            waiting = [c for c in live if c.seen == 0 and c.sent < t_close]
            if closed or not waiting or now > t_close + 60.0:
                break
        if door.crashed is not None:
            raise RuntimeError(f"front door crashed: {door.crashed!r}")
        wake = now + poll_s
        if not closed and pending:
            wake = min(wake, pending[0][0])
        time.sleep(max(0.0, wake - time.perf_counter()))
    t_end = time.perf_counter()
    for c in live:
        door.cancel(c.stream.rid)
    door.drain(timeout=120.0)
    for c in live:
        if c.reason is None:
            c.reason = c.stream.finish_reason or repr(c.stream.error)
            c.done = float("nan")
    gc.unfreeze()
    return dict(door=door, recorder=rec, clients=clients, t_start=t_start,
                t_open=t_open, t_close=t_close, t_end=t_end,
                tokens_in_window=tokens_in_window)


def warm(engine, mix: Dict, params, mark=lambda name: None, *,
         keep_text: bool = False) -> Optional[str]:
    """Run once every program and eager operation the scheduler will
    call under this mix, at every shape the mix can produce: each
    (group size, prompt length) prefill with the first-token pick and
    the inserts after it, the router probe per prompt length, the fused
    decode scan, eviction, and the admission ranking for every queue
    length up to ``max_queue``. The compiles are first made in parallel
    (ahead of time, into the persistent cache), then each program runs
    once so that the window finds it loaded. With `keep_text`, returns
    the compiled fused decode program's text, which names the scope of
    each of its instructions (``tracing.load``)."""
    import concurrent.futures as cf
    import os

    import jax
    import jax.numpy as jnp
    from repro.core.selection import rank_by_affinity
    from repro.models import init_cache
    from repro.serving.sampler import sample_step
    from repro.serving.step import NO_FAULT
    from traffic import warm_shapes

    fns, cfg = engine._fns, engine.cfg
    slots, B = mix["slots"], mix["slots"]
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    shapes = warm_shapes(mix)
    cache = init_cache(cfg, slots, engine.cache_len, dtype)
    key = jax.random.PRNGKey(0)
    tok = jnp.zeros((B,), jnp.int32)
    rem = jnp.zeros((B,), jnp.int32)
    fault = jnp.asarray(NO_FAULT, jnp.int32)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    pspec = jax.tree.map(spec, params)
    jobs = [lambda g=g, t=t: fns.prefill.lower(
        pspec, jax.ShapeDtypeStruct((g, t), jnp.int32)).compile()
        for g, t in shapes]
    jobs.append(lambda: fns.fused.lower(
        pspec, spec(tok), jax.tree.map(spec, cache), spec(rem), spec(key),
        spec(fault)).compile())
    with cf.ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        compiled = [f.result() for f in [ex.submit(j) for j in jobs]]
    fused_text = compiled[-1].as_text() if keep_text else None
    del compiled
    mark("warm_compile")

    for g, t in shapes:
        prompts = np.zeros((g, t), np.int32)
        lg, req_cache, _ = fns.prefill(params, prompts)
        key, k = jax.random.split(key)
        toks0 = sample_step(lg, k, temperature=engine.temperature)
        np.asarray(toks0)
        for i in range(g):
            cache = fns.insert(cache, req_cache, jnp.asarray(i % slots,
                                                             jnp.int32),
                               jnp.asarray(i, jnp.int32))
            tok = tok.at[i % slots].set(toks0[i])
        del req_cache
    mark("warm_prefill")
    for t in sorted({t for _, t in shapes}):
        np.asarray(fns.probe(params, np.zeros((1, t), np.int32)),
                   np.float64)
    key, k = jax.random.split(key)
    out = fns.fused(params, tok, cache, rem, k, fault)
    jax.tree.map(np.asarray, out[2:])
    cache = fns.evict(out[1], jnp.asarray(0, jnp.int32))
    mark("warm_probe_fused")
    E = cfg.moe.num_experts
    for n in range(1, mix["max_queue"] + 1):
        np.asarray(rank_by_affinity(jnp.zeros((n, E), jnp.float32),
                                    jnp.ones((E,), jnp.float32)))
    jax.block_until_ready(cache)
    mark("warm_rank")
    return fused_text
