"""Whether what the timed path served is correct: a comparison with the
architecture's float32 reference (``arch/<module>.py``, compared by
``reference.served_gaps``) of a sample of finished requests.

The number compared is the widest gap, over the served tokens, by which
a served token's reference logit lies below the reference's best logit
at that position (0 where the engine served the reference's greedy
token). Decoding is greedy, so a sound engine only departs from the
reference where two logits are closer than its rounding.

Positions where the reference's own routing is a near tie are left out:
where the k-th and (k+1)-th router logits of some layer lie closer than
``tie_margin``, bfloat16 rounding may route the token to the other expert,
which changes that position's output by the difference of two experts
and is no fault. The rule reads the reference alone; the share it leaves
out is printed. The limits are in the configuration's ``check`` entry,
set from the readings in ``PERF.md``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import arch
from benchmarks.chip.reference import served_gaps


def sample(done: list, seed: int, tokens: int, slots: int) -> list:
    """Finished requests drawn from the seed until their served tokens
    reach ``tokens``: the longest first, then one from each quarter of the
    slot range in turn, so every part of the batch that served requests is
    read (the engine fills the lowest free slot first)."""
    if not done:
        return []
    order = sorted(done, key=lambda t: -len(t.req.out_tokens))
    rng = np.random.default_rng([seed, 7])
    groups: dict = {}
    for i in rng.permutation(len(order) - 1):
        t = order[1 + i]
        g = -1 if t.slot is None else 4 * t.slot // slots
        groups.setdefault(g, []).append(t)
    queues = [groups[g] for g in sorted(groups)]
    picked, n = [order[0]], len(order[0].req.out_tokens)
    while n < tokens and any(queues):
        for q in queues:
            if q and n < tokens:
                t = q.pop(0)
                picked.append(t)
                n += len(t.req.out_tokens)
    return picked


def padded_prompt(prompt, padded: int) -> np.ndarray:
    """The prompt as the engine ran it: left-padded with id 0."""
    p = np.asarray(prompt, np.int32)
    return np.concatenate([np.zeros(padded - len(p), np.int32), p])


def readings(cfg: dict, params, inputs, control=False) -> dict:
    """Widest served-token gap over the positions kept, and how many were
    kept and left out; with ``control`` also the fp8 control's gap."""
    ref = arch.find(cfg).Reference(cfg, params)
    tie = cfg["check"]["tie_margin"]
    out = {}
    gaps, margin = served_gaps(ref, inputs)
    keep = margin >= tie
    out["max_logit_gap"] = float(gaps[keep].max()) if keep.any() else 0.0
    out["compared_tokens"] = int(keep.sum())
    out["near_ties_left_out"] = int((~keep).sum())
    # The widest gap among positions whose smallest router margin is at
    # least each of these: how far the near ties reach.
    out["gap_by_margin"] = {m: float(gaps[margin >= m].max(initial=0.0))
                            for m in (0.0, 0.005, 0.01, 0.02, 0.05, 0.1)}
    if control:
        cg, _ = served_gaps(ref, inputs, fp8_control=True)
        out["control_gap"] = float(cg[keep].max()) if keep.any() else 0.0
    return out


def judge(cfg: dict, gap: float, compared: int) -> dict:
    """The verdict on one reading of the widest gap: correct where it is
    within its limit over enough compared tokens."""
    lim = cfg["check"]
    numbers = {
        "max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
        "compared_tokens": {"value": compared,
                            "limit": lim["min_compared_tokens"]},
    }
    correct = (gap <= lim["max_logit_gap"]
               and compared >= lim["min_compared_tokens"])
    return {"correct": bool(correct), "numbers": numbers}


def compare(cfg: dict, params, inputs, control: bool = False) -> dict:
    """``correct`` and the numbers compared, of the program or, with
    ``control``, of the fp8 control put in its place."""
    r = readings(cfg, params, inputs, control=control)
    print(f"check: {len(inputs)} requests, {r['compared_tokens']} served "
          f"tokens compared, {r['near_ties_left_out']} left out as router "
          f"near ties", flush=True)
    out = judge(cfg, r["max_logit_gap"], r["compared_tokens"])
    if control:
        out["control"] = judge(cfg, r["control_gap"], r["compared_tokens"])
        out["readings"] = r
    return out
