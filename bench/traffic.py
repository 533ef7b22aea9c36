"""Open-loop request schedules from a traffic file and a seed.

Every seed gets the same work: sizes and inter-arrival gaps taken at
evenly spaced quantiles of the file's distributions, in the one order that
the file's ``arrangement_seed`` draws. The seed draws the prompts' token
ids. A window holds some hundred requests, too few for the order of bursts
against long requests to average out, so an order drawn from the seed
would make some seeds' work harder than others'. The gaps are scaled so
that the schedule spans the window exactly: ``rate * seconds`` requests,
the first due at 0.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np
from scipy import stats


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float            # offset from the start of the window
    prompt: np.ndarray      # (plen,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def gap_set(n: int, rate: float, cv: float) -> np.ndarray:
    """n inter-arrival gaps of a gamma law with mean 1/rate and
    coefficient of variation ``cv``, at evenly spaced quantiles."""
    shape = 1.0 / (cv * cv)
    return stats.gamma.ppf(_quantiles(n), shape, scale=1.0 / (rate * shape))


def length_set(n: int, spec: dict) -> np.ndarray:
    """n lengths of a lognormal law (``median``, ``sigma``) clipped to
    [``min``, ``max``] and rounded to a multiple of ``grid``."""
    x = stats.lognorm.ppf(_quantiles(n), spec["sigma"], scale=spec["median"])
    grid = int(spec.get("grid", 1))
    x = np.round(np.clip(x, spec["min"], spec["max"]) / grid) * grid
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def schedule(traffic: dict, rate: float, seconds: float, seed: int,
             vocab_size: int) -> List[Request]:
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(traffic["arrangement_seed"])
    gaps = order.permutation(gap_set(n, rate, traffic["arrival"]["cv"]))
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = order.permutation(length_set(n, traffic["prompt"]))
    outs = order.permutation(length_set(n, traffic["output"]))
    rng = np.random.default_rng(seed)
    return [Request(i, float(due[i]),
                    rng.integers(0, vocab_size, int(plens[i]),
                                 dtype=np.int32),
                    int(outs[i]))
            for i in range(n)]


def prompt_lengths(traffic: dict) -> List[int]:
    """Every prompt length the mix can send (the grid between its
    bounds): the prefill shapes a serve cell must warm."""
    p = traffic["prompt"]
    grid = int(p.get("grid", 1))
    return list(range(int(p["min"]), int(p["max"]) + 1, grid))
