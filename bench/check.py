"""What decides ``correct``: the served tokens against the plain reference,
each routed window against the capacity it was given, and every request
finished.

* ``logit_gap`` — once the window has closed and the program's state is
  freed: a seed-drawn sample of the requests the full-width member
  finished, the longest among them, packed into one ``PACK``-position row
  (prompt + served tokens).  The fp32 reference of the member's family
  (``bench.families``) runs once over the row; at every served position
  the gap is the reference's best logit less the logit of the token the
  server emitted.  The number compared is the widest gap.  Greedy tokens
  only: the engine samples by argmax.
* ``over_capacity`` — requests a routed window assigned to an endpoint
  beyond its free slots, summed over windows; and ``bad_column``, routed
  columns outside the pool.  Both exact: limit 0.
* ``failed`` — requests due in the window that were refused or did not
  finish by the drain limit.  Exact: limit 0.

The limits and the readings they were set from are in ``PERF.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench import families, weights

PACK = 2048       # positions of the packed reference row


@dataclasses.dataclass
class Served:
    """One finished request of the full-width member."""
    prompt: np.ndarray
    output: np.ndarray


def pack(served: List[Served], seed: int, n_pos: int = PACK):
    """Seed-drawn sample, longest first, packed into one row.  Returns
    (tokens, seg, pos, targets) where ``targets[i]`` is the served token
    that position ``i`` predicted, or -1."""
    if not served:
        return None
    rs = np.random.RandomState(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3]).generate_state(1)[0])
    length = [len(s.prompt) + len(s.output) - 1 for s in served]
    longest = int(np.argmax(length))
    order = [longest] + [i for i in rs.permutation(len(served))
                         if i != longest]
    tokens = np.zeros(n_pos, np.int32)
    seg = np.full(n_pos, -1, np.int32)
    pos = np.zeros(n_pos, np.int32)
    targets = np.full(n_pos, -1, np.int32)
    at = 0
    for n, i in enumerate(order):
        s = served[i]
        seq = np.concatenate([s.prompt, s.output[:-1]]).astype(np.int32)
        if at + len(seq) > n_pos:
            if n == 0:
                raise ValueError(f"longest request ({len(seq)}) exceeds the "
                                 f"packed row ({n_pos})")
            continue
        tokens[at:at + len(seq)] = seq
        seg[at:at + len(seq)] = n
        pos[at:at + len(seq)] = np.arange(len(seq))
        first = at + len(s.prompt) - 1
        targets[first:first + len(s.output)] = s.output
        at += len(seq)
    return tokens, seg, pos, targets


def gaps(ref_logits: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """Reference best logit less the logit of each target token."""
    rows = np.nonzero(targets >= 0)[0]
    lg = ref_logits[rows]
    return lg.max(-1) - lg[np.arange(len(rows)), targets[rows]]


def logit_gap(cfg: dict, seed: int, served: List[Served]) -> Dict:
    """The reference comparison; returns the widest gap and how many served
    tokens it covered."""
    packed = pack(served, seed)
    if packed is None:
        return {"logit_gap": float("inf"), "tokens_compared": 0}
    tokens, seg, pos, targets = packed
    w = weights.make(cfg, seed)
    lg = np.asarray(families.load(cfg).logits(cfg, w, tokens, seg, pos))
    g = gaps(lg, targets)
    return {"logit_gap": float(g.max()), "tokens_compared": int(len(g))}


def control_gap(cfg: dict, seed: int, served: List[Served]) -> Dict:
    """The control: at the same prompts and served tokens, the gap (under
    the fp32 reference) of the token that the float8 reference puts
    first."""
    tokens, seg, pos, targets = pack(served, seed)
    w = weights.make(cfg, seed)
    logits = families.load(cfg).logits
    ref = np.asarray(logits(cfg, w, tokens, seg, pos))
    low = np.asarray(logits(cfg, w, tokens, seg, pos, lowp=True))
    rows = targets >= 0
    pick = np.where(rows, low.argmax(-1), -1)
    g = gaps(ref, pick)
    return {"logit_gap": float(g.max()), "tokens_compared": int(len(g))}


def windows(records) -> Dict:
    """Capacity check of the routed windows (``clock.Routed`` records)."""
    over = bad = 0
    for r in records:
        loads, counts = np.asarray(r.batch.loads), np.asarray(r.batch.counts)
        m = len(loads)
        x = np.asarray(r.x)
        bad += int(np.sum((x < 0) | (x >= m)))
        x = x[(x >= 0) & (x < m)]
        free = np.maximum(loads - counts, 0)
        over += int(np.sum(np.maximum(np.bincount(x, minlength=m) - free,
                                      0)))
    return {"over_capacity": over, "bad_column": bad}


def verdict(numbers: Dict, limits: Dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
