"""The one general generator of serving traffic, driven by a mix's data
file.  Parameters of a mix (``traffic/<mix>.json``, kind "serve_open"):

    rate_rps      requests per second, fixed in the cell
    arrivals      {"process": "poisson"} or {"process": "gamma", "cv": 3}
    prompt_len    {"dist": "lognormal", "median", "sigma", "min", "max"}
    output_len    | {"dist": "uniform", "min", "max"} | {"dist": "fixed", "value"}
    shared_prefix optional {"count", "length", "share"}: a pool of
                  ``count`` prefixes of ``length`` tokens; a request
                  starts with one of them with probability ``share``
    shape_seed    fixes the gaps and the lengths

Every ``--seed`` gets the same requests (due time, prompt length, output
length) in the same order, with other token ids (and, from the driver,
other weights): the seed changes neither the amount of work nor how the
bursts and the long requests fall together, which is what a tail depends
on.  Measured on the chip (PERF.md section 6): with the order permuted or
rotated by the seed, the p90 of TTFT spread 5-20% from seed to seed while
two runs of one seed agreed within 2%.
"""
import math
from typing import NamedTuple

import numpy as np


class Request(NamedTuple):
    due_s: float
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def _lengths(spec, n, rng):
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.randint(spec["min"], spec["max"] + 1, n)
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def _gaps(spec, rate, n, rng):
    process = spec["process"]
    if process == "poisson":
        return rng.exponential(1.0 / rate, n)
    if process == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {process!r}")


def schedule(mix, seed, seconds, vocab_size):
    """The requests due in [0, seconds), in order of their due time."""
    rate = float(mix["rate_rps"])
    n = int(rate * seconds)
    if n < 1:
        raise ValueError("the mix sends no request in the window")
    shape = int(mix.get("shape_seed", 0))
    gaps = _gaps(mix["arrivals"], rate, n, np.random.RandomState(shape))
    # the last request falls due half a mean gap before the window closes
    gaps *= (seconds - 0.5 / rate) / gaps.sum()
    prompts = _lengths(mix["prompt_len"], n, np.random.RandomState(shape + 1))
    outputs = _lengths(mix["output_len"], n, np.random.RandomState(shape + 2))
    due = np.cumsum(gaps)
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    shared = mix.get("shared_prefix")
    pool = [rng.randint(0, vocab_size, shared["length"]).astype(np.int32)
            for _ in range(shared["count"])] if shared else []
    out = []
    for t, p, o in zip(due, prompts, outputs):
        ids = rng.randint(0, vocab_size, int(p)).astype(np.int32)
        if pool and rng.random_sample() < shared["share"]:
            prefix = pool[rng.randint(len(pool))][:max(int(p) - 1, 0)]
            ids[:len(prefix)] = prefix
        out.append(Request(float(t), ids, int(o)))
    return out


def percentile(values, q):
    """Nearest-rank percentile of all the values (no interpolation)."""
    ordered = sorted(values)
    return ordered[max(math.ceil(q / 100.0 * len(ordered)) - 1, 0)]
