"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``bench/traffic/``; this module turns it and ``--seed`` into the
request stream a run offers.

Keys of a traffic file:

- ``loop``: ``"closed"`` keeps the engine's queue one pool deep, the
  pool filled one request at a time so that completions spread over the
  window (a batch job that keeps a server full); ``"open"`` submits each
  request when it is due, whatever the server does (independent users).
- ``rate_per_s`` (open): mean arrival rate of a Poisson process. The
  gaps between arrivals are the exponential distribution's quantiles at
  ``(k + 0.5) / K`` for the ``K = round(rate * seconds)`` arrivals of a
  window, scaled to fill it exactly, in an order drawn from the seed:
  every seed offers the same work in a window, and the seed changes only
  its order, the labels and the noise.
- ``steps``: sampler steps of every request; ``guidance``: CFG scale.
- ``wait_s`` (open): how long past the window's close the run waits for
  requests that were due in the window.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    index: int            # position in the stream
    label: int
    noise_seed: int       # uint32
    steps: int
    guidance: float


def stream(traffic: dict, n_classes: int, seed: int) -> Iterator[Req]:
    """The seed's request stream: labels uniform over the classes, noise
    seeds uniform over uint32, both from ``seed`` alone."""
    rng = np.random.default_rng([int(seed), 1])
    i = 0
    while True:
        labels = rng.integers(0, n_classes, 256)
        seeds = rng.integers(0, 2 ** 32, 256, dtype=np.uint64)
        for lab, sd in zip(labels, seeds):
            yield Req(i, int(lab), int(sd), int(traffic["steps"]),
                      float(traffic["guidance"]))
            i += 1


def arrival_offsets(traffic: dict, seed: int, seconds: float,
                    before: float = 0.0, after: float = 0.0) -> List[float]:
    """Due times, in seconds from the window's start, of the open loop's
    arrivals from ``-before`` to ``seconds + after``. Inside the window
    the gaps are the seed's permutation of the exponential quantiles;
    the stretches before and after repeat that construction with their
    own permutations."""
    rate = float(traffic["rate_per_s"])
    rng = np.random.default_rng([int(seed), 2])

    def gaps(span: float) -> np.ndarray:
        k = max(1, int(round(rate * span)))
        q = -np.log1p(-(np.arange(k) + 0.5) / k)
        return rng.permutation(q * (span / q.sum()))

    def starts(span: float) -> np.ndarray:
        g = gaps(span)
        return np.concatenate([[0.0], np.cumsum(g)[:-1]])

    pre = starts(before) - before if before > 0 else np.zeros(0)
    win = starts(seconds)
    post = starts(after) + seconds if after > 0 else np.zeros(0)
    return [float(t) for t in np.concatenate([pre, win, post])]
