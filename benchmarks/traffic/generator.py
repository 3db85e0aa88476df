"""The one general traffic generator.  A mix is data: the ``traffic`` object
of a cell's file under ``benchmarks/workloads/``.  Every seed gets the SAME
lengths and arrival gaps in the SAME order (drawn once from the mix's own
fixed ``population_seed``) and its own token contents, so a seed never
changes the amount of work.  The order is part of the work: it decides which
retirements fall inside the window and so how many prefill ticks run (a
seeded order moved ``serve_tok_s`` by 7% between seeds, PERF.md PR 23).

Distributions (``{"dist": ..., ...}``): ``uniform`` (lo, hi),
``log_uniform`` (lo, hi), ``log_normal`` (median, sigma, lo, hi: clipped),
``exponential`` (mean).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _rng(seed: int) -> np.random.RandomState:
    # seeds run past 2**31; RandomState takes 32 unsigned bits
    return np.random.RandomState([seed & 0xFFFFFFFF, seed >> 32])


def draw(spec: Dict[str, Any], n: int, rng: np.random.RandomState) -> np.ndarray:
    d = spec["dist"]
    if d == "uniform":
        return rng.uniform(spec["lo"], spec["hi"], n)
    if d == "log_uniform":
        return np.exp(rng.uniform(math.log(spec["lo"]), math.log(spec["hi"]), n))
    if d == "log_normal":
        v = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        return np.clip(v, spec["lo"], spec["hi"])
    if d == "exponential":
        return rng.exponential(spec["mean"], n)
    raise ValueError(f"unknown distribution {d!r}")


def population(spec: Dict[str, Any], n: int, population_seed: int,
               stream: int) -> np.ndarray:
    """n values: the mix's own fixed sequence, the same for every seed."""
    return draw(spec, n, _rng(population_seed * 16 + stream))


def requests(mix: Dict[str, Any], vocab: int, seed: int) -> List[Dict[str, Any]]:
    """The requests of a serving mix, in the order they are sent.  Each is
    ``{"tokens": [...], "max_new_tokens": n, "due_s": t}``; ``due_s`` is None
    in a closed loop (a client sends its next when its last returns)."""
    n = int(mix["population"])
    ps = int(mix.get("population_seed", 0))
    plen = np.rint(population(mix["prompt_len"], n, ps, 0)).astype(int)
    olen = np.rint(population(mix["output_len"], n, ps, 1)).astype(int)
    cap = mix.get("max_total")
    if cap is not None:
        olen = np.minimum(olen, cap - plen)
    if mix["kind"] == "open_loop":
        gaps = population({**mix["arrival_gap"],
                           "mean": 1.0 / mix["rate_per_s"]}, n, ps, 2)
        due: List[Any] = list(np.cumsum(gaps) - gaps[0])
    elif mix["kind"] == "closed_loop":
        due = [None] * n
        wave = int(mix.get("first_wave", 0))
        if wave:
            # stagger the first wave's retirements: lengths x U(0,1], so the
            # window starts stationary.  The scaled lengths too are the
            # mix's own (an even grid of factors, paired by its own seed):
            # within a window most retirements are the first wave's, and
            # they set how many prefill ticks run.
            u = (np.arange(wave) + 0.5) / wave
            first = draw(mix["output_len"], wave, _rng(ps * 16 + 5))
            first = np.maximum(1, np.rint(
                first * u[_rng(ps * 16 + 6).permutation(wave)])).astype(int)
            olen[:wave] = first
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    rng = _rng(seed * 16 + 4)
    return [{"tokens": rng.randint(0, vocab, size=int(p)).tolist(),
             "max_new_tokens": int(max(1, o)),
             "due_s": None if t is None else float(t)}
            for p, o, t in zip(plen, olen, due)]


def train_batch(mix: Dict[str, Any], vocab: int, seed: int, step: int,
                global_batch: int) -> Dict[str, np.ndarray]:
    """The batch of one training step: every row differs, every step
    differs, the same seed and step give the same rows."""
    rng = _rng(seed * 4096 + step)
    t = rng.randint(0, vocab, size=(global_batch, mix["seq"] + 1)).astype(np.int32)
    return {"tokens": t[:, :-1], "targets": t[:, 1:]}
