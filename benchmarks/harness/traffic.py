"""The one general request generator. A traffic mix is a data file of
parameters (a workload file's `requests` group); this module turns it and
`--seed` into the list of requests the clients cycle through.

Every seed gets the SAME set of (prompt length, output length) pairs, the
evenly spaced quantiles of the two distributions paired by a fixed shuffle,
in an order of its own, with token ids of its own. So the seed changes the
order of the work and never its amount."""
from __future__ import annotations

import math

import numpy as np


def _quantiles(spec, n):
    """`n` evenly spaced quantiles of a length distribution."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "log_uniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "fixed":
        v = np.full(n, lo, float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v).astype(int), lo, hi)


def make_requests(spec, vocab, seed):
    """[{prompt: int32 array, max_new_tokens, kw: sampling arguments}]:
    `spec["distinct"]` requests for the clients to take in turn, again from
    the first once all are used. Request i samples as
    `spec["sampling"][i % len]` says (even ones greedy, odd ones sampled,
    in the mixes this PR adds)."""
    n = int(spec["distinct"])
    plens = _quantiles(spec["prompt_len"], n)
    olens = _quantiles(spec["output_len"], n)
    olens = olens[np.random.default_rng(0).permutation(n)]   # fixed pairing
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    order = rng.permutation(n)
    sampling = spec["sampling"]
    out = []
    for i, j in enumerate(order):
        out.append({
            "prompt": rng.integers(1, vocab, int(plens[j])).astype(np.int32),
            "max_new_tokens": int(olens[j]),
            "kw": dict(sampling[i % len(sampling)]),
        })
    return out


def probe_prompt(spec, vocab, seed):
    """A seeded prompt of the mix's shortest length, the same length for
    every seed, so that the reference forward it is checked against is one
    program per cell."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    return rng.integers(1, vocab, int(spec["prompt_len"]["lo"])).astype(
        np.int32)
