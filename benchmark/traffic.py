"""The one general traffic generator. A mix is a data file of parameters
(``traffic/<name>.json``); this turns it and ``--seed`` into the requests of a
window.

Every seed gets the same multiset of sizes and the same multiset of gaps
between arrivals, in another order: the sizes are the quantiles of the mix's
distributions and the seed only permutes them. So two seeds offer the same
work, and runs differ by order alone."""

from __future__ import annotations

import math

import numpy as np

# a seed is any whole number up to a little over 2**31; numpy takes it whole
_STREAMS = {"order": 1, "tokens": 2, "gaps": 3, "warmup": 4, "check": 5}


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), _STREAMS[stream]])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` whole sizes at the mid-quantiles of ``dist``, ascending."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["lo"]), float(dist["hi"])
    kind = dist["dist"]
    if kind == "uniform":
        x = lo + (hi - lo) * u
    elif kind == "log_uniform":
        x = lo * (hi / lo) ** u
    elif kind == "fixed":
        x = np.full(n, lo)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.rint(x), math.ceil(lo), math.floor(hi)).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    # a closed loop draws from a pool that outlasts the window
    return max(mix["clients"],
               int(math.ceil(mix["requests_per_second_ceiling"] * seconds)))


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The window's requests in sending order: ``{"id", "due_s", "prompt",
    "max_new"}``. ``due_s`` is None in a closed loop (a client sends its
    next request when its last reply is in)."""
    n = n_requests(mix, seconds)
    order = rng(seed, "order")
    # a closed loop consumes as much of its pool as the system lets it, so
    # the pool is dealt in blocks that each hold the same sizes: whatever
    # prefix a window gets through, every seed has done the same work
    block = int(mix.get("block_requests", n))
    plens = np.concatenate([
        order.permutation(quantiles(mix["prompt_tokens"], block))
        for _ in range(-(-n // block))])[:n]
    news = np.concatenate([
        order.permutation(quantiles(mix["new_tokens"], block))
        for _ in range(-(-n // block))])[:n]
    toks = rng(seed, "tokens")
    if mix["kind"] == "open_loop":
        # Poisson arrivals: the gaps are the quantiles of the exponential
        # distribution, permuted; each request is due in the middle of its
        # gap, and the gaps together fill the window exactly
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng(seed, "gaps").permutation(gaps) * (seconds / gaps.sum())
        due = np.cumsum(gaps) - 0.5 * gaps
    else:
        due = [None] * n
    out = []
    for i in range(n):
        out.append({
            "id": i,
            "due_s": None if due[i] is None else float(due[i]),
            # id 0 is the program's padding id outside decode: not sent
            "prompt": toks.integers(1, vocab, size=int(plens[i])).tolist(),
            "max_new": int(news[i]),
        })
    return out


def warmup_requests(mix: dict, seed: int, vocab: int) -> list:
    """One request per entry of the mix's ``warmup`` list: the lengths that
    reach every program the window's lengths can reach."""
    toks = rng(seed, "warmup")
    return [{"id": -1 - i, "due_s": None,
             "prompt": toks.integers(1, vocab, size=int(w["prompt_tokens"]))
             .tolist(),
             "max_new": int(w["new_tokens"])}
            for i, w in enumerate(mix["warmup"])]
