"""The one traffic generator: every mix is a data file of parameters.

Sizes and arrival gaps come from the mix's own ``shape_seed``, so every run
seed gets the same multiset of prompt lengths, output lengths, prefixes and
gaps; the run seed only shuffles their order and draws the token ids.  Runs
with different seeds then do the same amount of work.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64,
                                                         stream]))


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole numbers from ``spec``: ``uniform`` over [min, max], or
    ``lognormal`` (``median``, ``sigma``) redrawn until inside [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = np.empty(0, np.int64)
    while len(out) < n:
        x = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"], 4 * n))
        out = np.concatenate([out, x[(x >= lo) & (x <= hi)].astype(np.int64)])
    return out[:n]


def request_count(mix: dict, seconds: float) -> int:
    """Requests of one run: the backlog, or the arrivals of the window."""
    if "requests" in mix:
        return int(mix["requests"])
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """Prompts, output lengths and due times (seconds after the window
    opens; all 0 for a backlog) of one run, plus any shared prefixes."""
    shape = rng_for(mix["shape_seed"], 0)
    n = request_count(mix, seconds)
    # a mix may draw fewer distinct prompt lengths than requests and use
    # each of them alike often: every distinct length costs the program
    # compiles, which every cold set-up pays (the mix file says why it
    # sets this, and until when)
    k = min(n, int(mix.get("distinct_prompt_lengths", n)))
    turn = np.resize(draw(mix["prompt"], k, shape), n)
    out = draw(mix["output"], n, shape)
    prefix_of = np.full(n, -1)
    prefix_lens = []
    if "prefixes" in mix:
        pre = mix["prefixes"]
        prefix_lens = [int(x) for x in draw(pre["length"], pre["count"], shape)]
        pop = 1.0 / np.arange(1, pre["count"] + 1) ** pre["zipf"]
        prefix_of = shape.choice(pre["count"], size=n, p=pop / pop.sum())
    if "rate_per_s" in mix:
        gaps = shape.exponential(1.0 / mix["rate_per_s"], size=n)
        gaps *= seconds / gaps.sum()       # the n arrivals fill the window
    else:
        gaps = np.zeros(n)

    run = rng_for(seed, 1)
    order = run.permutation(n)
    gaps = gaps[run.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    prefixes = [run.integers(0, vocab, size=m).astype(np.int32)
                for m in prefix_lens]
    reqs = []
    for i, j in enumerate(order):
        body = run.integers(0, vocab, size=int(turn[j])).astype(np.int32)
        p = int(prefix_of[j])
        prompt = body if p < 0 else np.concatenate([prefixes[p], body])
        reqs.append({"rid": i, "prompt": prompt, "max_new_tokens": int(out[j]),
                     "due": float(due[i]), "prefix": p})
    return {"requests": reqs, "prefixes": prefixes}
