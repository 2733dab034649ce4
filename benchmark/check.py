"""The comparison that decides ``correct`` for a served model.

After a window has closed, a sample of the requests it finished (drawn from
the seed, the longest in it) is replayed through the configuration's plain
reference: once over each prompt with the tokens that were served. At every
served position the reference has a best logit; the number compared is the
widest gap by which a served token's reference logit lies below that best.
A token altered anywhere on the served path, a cache page read from the
wrong place or arithmetic below the stated precision all widen it.

The control is the same reading with the reference itself computed in the
configuration's ``lower_precision_control`` put in the program's place: at
each position, the gap of the token the lower precision puts first. Limits
sit between the two (``limits/<cell>.json`` gives the readings they were set
from). A limits file names the readings its cell is held to; the mean gap
(``logit_gap_mean``) is the one that decides where experts route."""

from __future__ import annotations

import json

import numpy as np

from . import spec, traffic


def limits_for(cell_name: str) -> dict:
    return json.loads((spec.DATA / "limits" / f"{cell_name}.json")
                      .read_text())["limits"]


def sample(records: list, seed: int, n: int) -> list:
    """``n`` finished requests: the longest (prompt and answer together),
    and the rest drawn from the seed."""
    done = [r for r in records if not r["error"] and r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: r["id"])
    longest = max(done, key=lambda r: r["prompt_tokens"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    pick = traffic.rng(seed, "check").permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in pick]


def gaps(cell: spec.Cell, weights: dict, prompts: dict, sampled: list,
         control: str | None = None) -> dict:
    """The reference's readings over ``sampled``. ``prompts`` maps a
    request's id to its prompt. Returns ``served`` (one gap per served
    token) and, with ``control``, ``control`` (one gap per position for the
    token that precision puts first)."""
    import jax.numpy as jnp

    cfg = cell.config
    ref = spec.plugin("reference", cfg["reference"])
    T = int(cfg["n_positions"])
    K = int(cell.traffic["new_tokens"]["hi"])
    kw = dict(n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"])
    out = {"served": [], "control": []}
    for r in sampled:
        prompt, toks = prompts[r["id"]], r["tokens"]
        n, p = len(toks), len(prompt)
        ids = np.zeros((T,), np.int32)
        ids[:p + n - 1] = (list(prompt) + list(toks))[:p + n - 1]
        at = np.zeros((K,), np.int32)
        at[:n] = np.arange(p - 1, p + n - 1)
        logits = ref.logits_at(weights, jnp.asarray(ids), jnp.asarray(at),
                               precision="float32", **kw)
        best = logits.max(axis=-1)
        served = jnp.take_along_axis(
            logits, jnp.asarray(np.pad(toks, (0, K - n)))[:, None], axis=1)
        out["served"] += np.asarray(best - served[:, 0])[:n].tolist()
        if control:
            low = ref.logits_at(weights, jnp.asarray(ids), jnp.asarray(at),
                                precision=control, **kw)
            first = jnp.argmax(low, axis=-1)
            got = jnp.take_along_axis(logits, first[:, None], axis=1)
            out["control"] += np.asarray(best - got[:, 0])[:n].tolist()
    return out


def compare(readings: dict, limits: dict) -> tuple:
    """Each number the cell's limits file names, beside its limit. Returns
    (correct, lines for standard error, {name: {"value", "limit"}} for the
    result's line)."""
    ok, lines, compared = True, [], {}
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and value <= limit
        ok = ok and good
        lines.append(f"check {name}: {value!r} (limit {limit!r}) "
                     f"{'ok' if good else 'NOT OK'}")
        compared[name] = {"value": value, "limit": limit}
    return ok, lines, compared


def serving_readings(served_gaps: list, win, sampled: list) -> dict:
    """The numbers compared in a serving cell."""
    e = win.engine
    return {
        # widest gap, over the sampled served tokens, between the
        # reference's best logit and its logit for the token served
        "logit_gap_max": max(served_gaps) if served_gaps else None,
        # the same gap on average over those tokens. Where experts route,
        # the widest gap is a flipped expert choice in the sound program
        # and in the lower-precision control alike, and the mean separates
        # them ten to one (limits/glm-4.7-flash.rag.json)
        "logit_gap_mean": (sum(served_gaps) / len(served_gaps)
                           if served_gaps else None),
        # exact: every sampled request got as many tokens as it asked for
        "short_answers": sum(len(r["tokens"]) != r["max_new"]
                             for r in sampled) if sampled else None,
        # exact: the smoke's engine checks (0 = all hold)
        "not_paged_engine": sum(not e[k] for k in
                                ("paged", "only_decoder", "open",
                                 "page_walk_kernel"))
        + int(e["snapshot_replayed"] > 0),
    }
