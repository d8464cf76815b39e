"""Open-loop request streams, drawn from the seed.

`zipf_seeds` is a copy of the program's `serving/loadgen.py` generator
(Zipf popularity over a random hot set), so that the traffic does not move
when the program does.  Arrivals are an open-loop process at a fixed rate:
``n = rate x seconds`` requests, each due at a time drawn uniformly over
the window and sorted, which is a Poisson process conditioned on its
count.  Every seed thus offers the same number of requests, in another
order and at other times.  With ``burst`` the same requests fall only in
the "on" phases of an on/off cycle, at the rate that keeps the mean.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["zipf_seeds", "Stream", "open_loop"]


def zipf_seeds(num_nodes: int, requests: int, *, zipf: float,
               hot_fraction: float, rng: np.random.Generator) -> np.ndarray:
    """Zipf-popularity seed nodes: ranks Zipf-weighted over a random node
    permutation, so a small hot set dominates the trace."""
    pool = max(1, int(num_nodes * hot_fraction))
    nodes = rng.permutation(num_nodes)[:pool]
    ranks = np.arange(1, pool + 1, dtype=np.float64)
    p = ranks ** (-zipf)
    p /= p.sum()
    return nodes[rng.choice(pool, size=requests, p=p)]


@dataclasses.dataclass(frozen=True)
class Stream:
    """``due[i]`` seconds after the window opens, request ``seeds[i]``."""

    due: np.ndarray
    seeds: np.ndarray


def _on_time_to_wall(t: np.ndarray, on_s: float, off_s: float) -> np.ndarray:
    cycles = np.floor(t / on_s)
    return cycles * (on_s + off_s) + (t - cycles * on_s)


def open_loop(num_nodes: int, mix: dict, rate: float, seconds: float,
              seed: int) -> Stream:
    """The stream of one window.  ``mix`` holds ``zipf``, ``hot_fraction``
    and optionally ``burst = {"on_s", "off_s"}``."""
    rng = np.random.default_rng([seed, 0x5E7E])
    n = max(1, int(round(rate * seconds)))
    burst = mix.get("burst")
    if burst:
        on, off = float(burst["on_s"]), float(burst["off_s"])
        on_total = seconds * on / (on + off)
        due = _on_time_to_wall(np.sort(rng.uniform(0.0, on_total, n)),
                               on, off)
    else:
        due = np.sort(rng.uniform(0.0, seconds, n))
    seeds = zipf_seeds(num_nodes, n, zipf=mix["zipf"],
                       hot_fraction=mix["hot_fraction"], rng=rng)
    return Stream(due=due, seeds=seeds.astype(np.int64))
