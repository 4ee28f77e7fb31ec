"""Decides ``correct``: the reference replays what the timed path did.

Under XShare a token's experts depend on every row decoding beside it,
so the reference cannot take requests one at a time. It replays the
served batch instead: every prefill group the scheduler ran (row by
row, prefill routes without a policy), every insert into a slot, and
every decode step of the fused scan with the slots that were live in it
(a slot is live at step j of a call while the remaining budget the
scheduler passed exceeds j). Each live slot feeds the token the client
received and the reference scores the token the client received next.

The number compared is the widest gap, over every token checked, by
which the served token's reference logit lies below the reference's
best logit: 0 when the program picks the reference's argmax, small when
rounding flips a near tie. The replay starts with the first prefill and
covers the first ``check_steps`` decode steps, so every request live in
them is checked on every token it got there.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np


def walk(events: List[tuple], num_slots: int) -> Iterator[tuple]:
    """The schedule, in order, from the recorder's events:

    ("prefill", prompts (G, T))   a group prefill
    ("slot", slot, row)           row of the last group enters slot
    ("step", live (B,), i, j)     decode step j of the fused call that
                                  is events[i]

    The scheduler adopts a whole group without inserts when it fills
    every slot of an empty batch; rows then enter slots 0..G-1."""
    pending = None
    for i, ev in enumerate(events):
        if ev[0] == "prefill":
            if pending is not None:
                yield from _adopt(pending, num_slots)
            pending = [ev[1], False]
            yield ("prefill", ev[1])
        elif ev[0] == "insert":
            pending[1] = True
            yield ("slot", ev[1], ev[2])
        else:
            if pending is not None:
                yield from _adopt(pending, num_slots)
                pending = None
            rem = ev[1]
            n = ev[2][0].shape[0]
            for j in range(n):
                yield ("step", rem > j, i, j)


def _adopt(pending, num_slots):
    prompts, inserted = pending
    if not inserted:
        if prompts.shape[0] != num_slots:
            raise RuntimeError(f"a group of {prompts.shape[0]} rows entered "
                               f"{num_slots} slots without inserts")
        for row in range(prompts.shape[0]):
            yield ("slot", row, row)


def replay(reference, events: List[tuple], served: Dict[bytes, list],
           num_slots: int, steps: int, *, control=None) -> Dict:
    """Walk the schedule through `reference` for the first `steps`
    decode steps. served: prompt bytes -> tokens the client received.

    Returns the gaps of the served tokens; with `control` (the
    reference's argmax per checked token, in order, from a replay in
    the control precision) also the gaps of the control's tokens."""
    gaps, ctrl_gaps, argmaxes = [], [], []
    slot_req: List = [None] * num_slots
    slot_k = np.zeros(num_slots, int)
    last_rows: List[Tuple] = []
    missing = 0
    done_steps = 0

    def score(logits, tok):
        nonlocal missing
        if tok is None:
            missing += 1
            return
        gaps.append(float(logits.max() - logits[tok]))
        argmaxes.append(int(logits.argmax()))
        if control is not None:
            c = control[len(argmaxes) - 1]
            ctrl_gaps.append(float(logits.max() - logits[c]))

    def token(key, k):
        toks = served.get(key)
        return None if toks is None or k >= len(toks) else int(toks[k])

    for item in walk(events, num_slots):
        if done_steps >= steps:
            break
        if item[0] == "prefill":
            last_rows = []
            for prompt in item[1]:
                key = prompt.tobytes()
                lg, kv = reference.prefill(prompt)
                score(lg, token(key, 0))
                last_rows.append((key, kv))
        elif item[0] == "slot":
            _, slot, row = item
            key, kv = last_rows[row]
            reference.insert(slot, kv)
            slot_req[slot], slot_k[slot] = key, 1
        else:
            live = np.asarray(item[1], bool)
            feed = np.zeros(num_slots, np.int64)
            for s in np.flatnonzero(live):
                t = token(slot_req[s], slot_k[s] - 1)
                feed[s] = 0 if t is None else t
            lg = reference.decode(feed, live)
            for s in np.flatnonzero(live):
                score(lg[s], token(slot_req[s], slot_k[s]))
                slot_k[s] += 1
            done_steps += 1
    return {"gaps": np.asarray(gaps), "control_gaps": np.asarray(ctrl_gaps),
            "argmax": argmaxes, "missing": missing, "steps": done_steps}
