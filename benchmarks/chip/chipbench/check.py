"""The comparison that decides `correct`.

After the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the longest one, is run through the plain float32 reference (`reference`,
with the steps of the configuration's architecture module)
over each prompt with its served tokens. A served token's gap is how far
its reference logit lies below the reference's best at that position
(greedy decoding serves the best token, so a sound program reads only
rounding there); the widest gap, the mean gap and the count of tokens that
were not the reference's first choice are read, and a cell's limits file
names the ones it compares. The control, the same reference computed in
int8 (`quant="int8"`), is read the same way at the same positions: the gap
of the token it puts first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from .reference import Reference, served_rows


def pad_lengths(traffic: dict) -> List[int]:
    """Fixed padded lengths for the reference: powers of two from 256 up
    to the longest prompt-with-answer the mix can send, rounded up to 128
    (fixed, so the reference compiles the same few shapes on every run)."""
    top = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    top = -(-top // 128) * 128
    out, n = [], 256
    while n < top:
        out.append(n)
        n *= 2
    return out + [top]


def sample(done: Sequence[dict], seed: int,
           max_requests: int) -> List[dict]:
    """The longest finished request, then up to max_requests - 1 others in
    a seeded order."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"])
                                   + len(done[i]["output"])))
    first, rest = order[0], order[1:]
    rest = [rest[i] for i in np.random.default_rng([int(seed), 3])
            .permutation(len(rest))]
    return [done[i] for i in ([first] + rest)[:max_requests]]


def readings(arch, c: dict, seed: int, picked: Sequence[dict],
             traffic: dict, control: bool = False) -> Dict[str, float]:
    """tokens_compared and served_logit_gap_{max,mean}, served_not_first;
    with control=True the same of the int8 reference's first choices
    (control_*), read against the float32 reference at the same
    positions."""
    seqs, rows, served = [], [], []
    for r in picked:
        s, rw = served_rows(r["prompt"], r["output"])
        seqs.append(s)
        rows.append(rw)
        served.extend(r["output"])
    if not served:
        return {"served_logit_gap_max": float("inf"),
                "served_logit_gap_mean": float("inf"), "tokens_compared": 0}
    lengths = pad_lengths(traffic)
    max_rows = int(traffic["output_tokens"]["max"])
    out = {"tokens_compared": len(served)}
    sets = [served]
    if control:
        q = Reference(arch, c, seed, quant="int8").evaluate(
            seqs, rows, lengths, max_rows)
        sets.append(q["argmax"].tolist())
    ref = Reference(arch, c, seed).evaluate(seqs, rows, lengths, max_rows,
                                            sets)
    gap = ref["best"][None, :] - ref["got"]
    for name, g in zip(("served", "control"), gap):
        out[f"{name}_logit_gap_max"] = float(g.max())
        out[f"{name}_logit_gap_mean"] = float(g.mean())
        out[f"{name}_not_first"] = int((g > 0).sum())
    return out


def judge(values: Dict[str, float], limits: Dict[str, dict]):
    """Each number beside its limit: {"max": x} or {"min": x}. Returns
    (all within their limits, {name: {"value", "limit"}})."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = values.get(name)
        if "max" in lim:
            good = v is not None and v <= lim["max"]
            limit = lim["max"]
        else:
            good = v is not None and v >= lim["min"]
            limit = lim["min"]
        ok &= bool(good)
        checks[name] = {"value": v, "limit": limit}
    return ok, checks


def control_limits(limits: Dict[str, dict]) -> Dict[str, dict]:
    """The cell's limits with each served_* number read as the control's
    (control_*): the control is judged by the comparison that judges the
    program, and has to come out as not correct."""
    return {("control_" + k[len("served_"):] if k.startswith("served_")
             else k): v for k, v in limits.items()}
