"""What decides ``correct``: the tokens the timed path served, against the
plain float32 reference that the cell's configuration file names
(``"reference"``, a module under ``bench/reference/``).

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is run through the reference
(prompt and served tokens, one forward pass each).  For each format the
cell serves, the number compared is the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position (0 where the served token is the reference's argmax), with the
logits of the compared tokens in float64.  The weights' lm_head twins
(``bench/weights.py``) put a token within float32-grade rounding of the
best at every position, so a path that computes below the format's
precision serves the wrong twin and reads a wider gap.  Every
request is greedy, so that is the whole check of what was served.  A
second number counts finished requests whose token count is not the
``max_new`` they asked for (limit 0).

A reference may return, beside each position's gap, whether it decided
that position (``compared``): where a discrete choice inside the model,
such as an expert's selection, lies within the reference's own rounding
of a tie, it may declare the position undecidable by a rule of its own,
documented there.  The widest gap is then taken over the compared served
positions only, and ``excluded_<format>`` is the share (0 to 1) of the
format's served positions in the sample that were not compared.  Like
every number, it fails without a limit of the cell's.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.traffic import rng_for

BUCKET = 256   # reference sequence lengths are padded to a multiple of this


def sample(tracks: Sequence, seed: int, spec: dict) -> List:
    """Per format: the longest finished request, then others in a seeded
    order, until the format has ``tokens / formats`` served tokens or
    ``max_requests_per_mode`` requests."""
    rng = rng_for(seed, "check")
    by_mode: Dict[str, List] = {}
    for t in tracks:
        by_mode.setdefault(t.req.mode, []).append(t)
    per_mode = spec["tokens"] / max(1, len(by_mode))
    out = []
    for mode in sorted(by_mode):
        ts = sorted(by_mode[mode], key=lambda t: t.req.idx)
        longest = max(ts, key=lambda t: (len(t.sreq.out), -t.req.idx))
        rest = [ts[i] for i in rng.permutation(len(ts)) if ts[i] is not longest]
        picked, n = [longest], len(longest.sreq.out)
        for t in rest:
            if n >= per_mode or len(picked) >= spec["max_requests_per_mode"]:
                break
            picked.append(t)
            n += len(t.sreq.out)
        out += picked
    return out


def _padded(prompt: np.ndarray, served: Sequence[int]) -> Tuple:
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    T = -(-len(seq) // BUCKET) * BUCKET
    tokens = np.zeros(T, np.int32)
    tokens[: len(seq)] = seq
    cand = np.zeros(T, np.int32)
    n = len(prompt)
    cand[n - 1: n - 1 + len(served)] = served
    return tokens, cand, slice(n - 1, n - 1 + len(served))


def widest_gaps(reference, params: dict, model: dict, picked: Sequence,
                precisions: Sequence[str] = ("highest",)
                ) -> Dict[str, float]:
    """``gap_<format>`` at ``highest`` (the program's served tokens), and
    ``gap_<format>@<precision>`` for each lower precision (that
    precision's own argmax: the control), by the module ``reference``'s
    ``gaps``.  Where it returns ``compared``, also ``excluded_<format>``
    (and ``@<precision>``): the share of served positions not compared."""
    out: Dict[str, float] = {}
    served: Dict[str, int] = {}
    excluded: Dict[str, int] = {}
    head_t = np.ascontiguousarray(np.asarray(params["lm_head"]["w"]).T)
    for t in picked:
        tokens, cand, sl = _padded(t.req.prompt, t.sreq.out)
        for prec in precisions:
            g, _, *compared = reference.gaps(params, head_t, tokens, cand,
                                             model, prec)
            suffix = t.req.mode + ("" if prec == "highest" else f"@{prec}")
            g = g[sl]
            if compared:
                mask = np.asarray(compared[0], bool)[sl]
                served[suffix] = served.get(suffix, 0) + len(g)
                excluded[suffix] = (excluded.get(suffix, 0)
                                    + int(np.sum(~mask)))
                g = g[mask]
            w = float(np.max(g)) if len(g) else 0.0
            out[f"gap_{suffix}"] = max(out.get(f"gap_{suffix}", 0.0), w)
    for suffix, n in served.items():
        out[f"excluded_{suffix}"] = excluded[suffix] / n
    return out


def wrong_length(tracks: Sequence) -> int:
    return sum(1 for t in tracks
               if t.sreq.state == "done" and len(t.sreq.out) != t.req.max_new)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each number beside its limit; ``correct`` when every number is at
    or under its limit.  A number without a limit fails."""
    checks = {}
    ok = True
    for name in sorted(numbers):
        v = numbers[name]
        lim = limits.get(name)
        if lim is None or not math.isfinite(v) or v > lim:
            ok = False
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
