"""The one traffic generator: reads a traffic file's parameters and makes the
requests of a run from its seed.

Every seed gets the same multiset of request sizes and the same set of
inter-arrival gaps, in another order, so two seeds differ in order and
prompt tokens but not in the amount of work:

* prompt and output lengths are the quantiles ``(i + 0.5) / n`` of their
  clipped lognormals;
* each request's format is drawn in the file's proportions by exact counts;
* lengths and formats are paired by one fixed shuffle, the same for every
  seed, so the multiset of (format, prompt, output) triples is fixed;
* the seed shuffles the order of the triples and of the gaps, and draws the
  prompt tokens.

An open loop sends request i at its due time, the running sum of the gaps
(exponential quantiles at the file's rate).  A closed loop deals the triples to its clients; a client sends its next
request when its last one has finished.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_PAIRING_SEED = 20191009  # fixes the (format, prompt, output) pairing


@dataclasses.dataclass
class Req:
    """One request as the generator makes it."""
    idx: int
    mode: Optional[str]
    prompt: np.ndarray          # (n,) int32 token ids
    max_new: int
    due: float = 0.0            # open loop: seconds after the stream starts
    client: int = -1            # closed loop: the client that sends it


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of one seed (any size of
    non-negative integer seed)."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng(np.random.SeedSequence([seed, tag]))


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    qs = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * z) for z in qs]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _mode_counts(modes: Dict[str, float], n: int) -> List[Optional[str]]:
    names = sorted(modes)
    raw = [modes[m] * n for m in names]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(names)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    out: List[Optional[str]] = []
    for name, c in zip(names, counts):
        out += [name] * c
    return out


def triples(spec: dict, n: int) -> List[tuple]:
    """The fixed multiset of ``n`` (format, prompt length, output length)
    triples of a traffic file, in the fixed pairing order."""
    fix = np.random.default_rng(_PAIRING_SEED)
    prompts = fix.permutation(_quantile_lengths(spec["prompt_len"], n))
    outs = fix.permutation(_quantile_lengths(spec["output_len"], n))
    modes = _mode_counts(spec["modes"], n)
    modes = [modes[i] for i in fix.permutation(n)]
    return [(m, int(p), int(o)) for m, p, o in zip(modes, prompts, outs)]


def n_requests(spec: dict, seconds: float) -> int:
    """How many requests a stream of ``seconds`` holds."""
    if spec["loop"] == "open":
        # 5% over, so that arrivals run on past the window's end
        return max(1, int(math.ceil(1.05 * spec["rate_per_s"] * seconds)))
    # closed loop: enough for every client to finish a request per mean
    # output length's worth of ticks at 100 ticks/s, twice over
    mean_out = spec["output_len"]["median"] * math.exp(
        spec["output_len"]["sigma"] ** 2 / 2)
    per_client = int(math.ceil(2 * seconds * 100 / mean_out)) + 2
    return spec["clients"] * per_client


def make_stream(spec: dict, vocab: int, seed: int, seconds: float
                ) -> List[Req]:
    """The requests of one run of ``seconds`` (pre-roll and window
    together), in send order for an open loop and in client order for a
    closed one."""
    n = n_requests(spec, seconds)
    base = triples(spec, n)
    rng = rng_for(seed, "traffic")
    order = rng.permutation(n)
    reqs = []
    for i, j in enumerate(order):
        mode, plen, olen = base[j]
        prompt = rng.integers(0, vocab, size=plen, dtype=np.int64)
        reqs.append(Req(idx=i, mode=mode, prompt=prompt.astype(np.int32),
                        max_new=olen))
    if spec["loop"] == "open":
        rate = spec["rate_per_s"]
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                         for i in range(n)])
        gaps = rng.permutation(gaps)
        due = np.cumsum(gaps) - gaps[0]
        for r, t in zip(reqs, due):
            r.due = float(t)
    else:
        for i, r in enumerate(reqs):
            r.client = i % spec["clients"]
    return reqs
