"""The one general traffic generator.  Standard library only: the load
generator's child process imports this and must never import JAX.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``):

    loop          "closed" (N clients, each sends its next request when the
                  previous one ends) or "open" (arrivals on a schedule)
    prompt/output {"median", "sigma", "min", "max"}: log-normal, clipped
    pool          closed loop: how many (prompt, output) pairs the pool
                  holds; they are cycled, so a window sees several whole
                  cycles.  Open loop: the pool is the window's arrivals,
                  ``round(rate * seconds)`` of them
    pool_seed     what pairs prompts with outputs and draws the gaps: NOT
                  the run's ``--seed``
    preroll_s     seconds of the same traffic sent before the window opens
    end_to_end    the end-to-end metrics a cell of this mix reports

Every ``--seed`` gets the SAME sizes (and, open loop, the same gaps
between arrivals) in the SAME cyclic order: pool and order are made from the
file alone, and the run's seed chooses where in the cycle the run starts
and every prompt's token ids.  So two seeds differ in their inputs and in
which request meets which, never in the amount or the mix of work.  (Measured:
with the order itself drawn from the seed, runs of one seed agreed within
1 % and seeds differed by up to 7 %.)  The sizes are the distribution's quantiles, not random draws: a
pool of n holds the (i + 1/2)/n quantiles of the clipped log-normal, so a
small pool still has the tails in their right proportion.  Prompt token
ids are random per request: no two requests share a prefix.
"""

from __future__ import annotations

import hashlib
import json
import math
import random
import statistics

LOOPS = ("closed", "open")
STRATA = 8          # layers of prompt length an order is balanced over


def rng_for(*salt) -> random.Random:
    """Deterministic RNG from a salt.  Not builtin ``hash``, which is
    salted per process (idea: tpuserve/replay/workload.py ``_rng``)."""
    digest = hashlib.sha256(":".join(str(s) for s in salt).encode()).digest()
    return random.Random(int.from_bytes(digest[:8], "big"))


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    for key in ("prompt", "output"):
        d = mix[key]
        if not (0 < d["min"] <= d["median"] <= d["max"]):
            raise ValueError(f"{path}: {key} needs 0 < min <= median <= max")
    return mix


def clipped_lognormal_quantiles(dist: dict, n: int) -> list:
    """The (i + 1/2)/n quantiles, i = 0..n-1, of the log-normal with this
    median and sigma, rounded and clipped to [min, max]."""
    normal = statistics.NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def bounds(mix: dict, n: int) -> dict:
    """The length bounds the warm-up shapes follow from: those of the
    pool of ``n`` that the cell really sends."""
    pool = size_pool(mix, n)
    prompts = [p for p, _ in pool]
    outputs = [o for _, o in pool]
    return {"prompt_min": min(prompts), "prompt_max": max(prompts),
            "output_max": max(outputs),
            "total_max": max(prompts) + max(outputs)}


def pool_size(mix: dict, rate: float, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, round(rate * seconds))
    return int(mix["pool"])


def size_pool(mix: dict, n: int) -> list:
    """``n`` (prompt_tokens, output_tokens) pairs, the same for every run
    seed."""
    prompts = clipped_lognormal_quantiles(mix["prompt"], n)
    outputs = shuffled(clipped_lognormal_quantiles(mix["output"], n),
                       "pairing", mix["pool_seed"])
    return list(zip(prompts, outputs))


def gap_pool(mix: dict, n: int, seconds: float) -> list:
    """``n`` gaps between Poisson arrivals, scaled so that they span the
    window exactly: the same multiset for every run seed."""
    rng = rng_for("gaps", mix["pool_seed"])
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    return [g * scale for g in gaps]


def shuffled(items: list, *salt) -> list:
    out = list(items)
    rng_for(*salt).shuffle(out)
    return out


def rotated(items: list, step: int, *salt) -> list:
    """``items`` started at a place the salt chooses, a multiple of
    ``step``."""
    turns = max(1, len(items) // max(1, step))
    k = step * rng_for(*salt).randrange(turns)
    return items[k:] + items[:k]


def sizes_for(mix: dict, seed: int, phase: str, n: int) -> list:
    """The size pool in the order of one phase ("preroll" or "window"),
    started where this seed starts it.  The order is a balanced shuffle
    made from ``pool_seed``: the pool, sorted by prompt length, is cut into
    ``STRATA`` equal layers, and every run of ``STRATA`` consecutive
    requests takes one from each layer, so long prompts never clump."""
    pool = sorted(size_pool(mix, n))
    strata = min(STRATA, n)
    per = -(-n // strata)
    fixed = ("order", mix["pool_seed"], phase)
    layers = [shuffled(pool[i * per:(i + 1) * per], *fixed, "layer", i)
              for i in range(strata)]
    out = []
    for turn in range(per):
        group = [layer[turn] for layer in layers if turn < len(layer)]
        out += shuffled(group, *fixed, "turn", turn)
    return rotated(out, strata, "start", seed, phase)


def arrivals_for(mix: dict, seed: int, phase: str, n: int,
                 seconds: float) -> list:
    """Offsets from the phase's start of the open loop's arrivals: the
    gap pool (one order for every seed), started where this seed starts
    it, accumulated.  The first arrival is at 0 and the last one gap
    before ``seconds``: n arrivals in [0, seconds)."""
    t, out = 0.0, []
    for g in rotated(gap_pool(mix, n, seconds), 1, "gaps", seed, phase):
        out.append(t)
        t += g
    return out


def prompt_ids(seed: int, phase: str, index: int, n: int, vocab: int) -> list:
    """Random ids in [1, vocab-2] (no specials), deterministic in the run
    seed and the request's index."""
    rng = rng_for("prompt", seed, phase, index)
    hi = max(vocab - 2, 1)
    return [rng.randint(1, hi) for _ in range(n)]
