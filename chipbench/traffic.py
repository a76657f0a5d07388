"""One general traffic generator, driven by a mix's data file.

A traffic file under ``chipbench/traffic/`` holds parameters only.  Every
mix is an open loop: requests fall due on a schedule, whatever the server
does.

* ``rate_rps``: the offered rate.  ``count = round(rate * seconds)``
  requests are due in a window of ``seconds``.
* ``arrival``: ``poisson``.  The gaps between due times are the
  ``count`` quantiles of the exponential distribution at
  ``(i + 0.5) / count``, scaled to fill the window, in a seeded order.
* ``length``: ``{"dist": "lognormal", "mu", "sigma", "elements_per_s",
  "min", "max"}``: a log-normal of seconds (``mu`` and ``sigma`` of the
  natural log), ``elements_per_s`` elements to a second, clipped to
  ``[min, max]`` elements.  The lengths are the ``count`` quantiles at
  ``(i + 0.5) / count``, in a seeded order.
* ``popularity``: ``{"dist": "zipf", "s", "ranking_seed"}``: the k-th
  kernel of a ranking fixed by ``ranking_seed`` takes a share ``k ** -s``
  (normalised) of the requests, rounded by largest remainder, in a seeded
  order.
* ``warmup_s``: seconds of the same mix, from a seed stream the window
  does not use, served before the window.
* ``events`` (a fleet's traffic only, optional): a list of
  ``{"at": <share of the window, 0..1>, "do": "drain", "replica": <index>}``
  and ``{"at": ..., "do": "add"}``.  At ``start + at * seconds`` of the
  window, in order with the submits, the harness drains that replica of
  the fleet (``ShardedOverlayServer.drain_replica``) or adds one
  (``add_replica``, on the device that hosts the fewest replicas: a
  drained one's), under the engine lock.  An index is the fleet's at
  that time.  Warm-up and ``sweep.py`` do not fire them.

So every seed offers the same set of gaps, sizes, kernels and tenants,
each in an order of its own.  A request's tenant is one of the
configuration's ``tenants``, each taking an equal share.  Inputs are
slices of per-kernel seeded blocks of ``block_len`` elements (the
configuration's): a request reads ``length`` consecutive elements from a
seeded offset of its kernel's block.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

#: seed streams: the input blocks, the warm-up schedule and the window's
#: schedule never share draws
STREAM_BLOCKS, STREAM_WARMUP, STREAM_WINDOW = 0, 1, 2


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


@dataclasses.dataclass
class Request:
    kernel: int            # index into the catalog
    tenant: str
    length: int
    offset: int
    due: float = 0.0       # seconds after the phase starts
    t_submit: float | None = None
    t_claim: float | None = None
    outs: list | None = None


def _quantiles(count: int) -> np.ndarray:
    return (np.arange(count) + 0.5) / count


def shares(weights: np.ndarray, count: int) -> np.ndarray:
    """``count`` split in proportion to ``weights``, by largest remainder."""
    exact = count * weights / weights.sum()
    n = np.floor(exact).astype(np.int64)
    n[np.argsort(n - exact, kind="stable")[:count - n.sum()]] += 1
    return n


def lengths(spec: dict, count: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(count)])
    n = spec["elements_per_s"] * np.exp(spec["mu"] + spec["sigma"] * z)
    return rng.permutation(np.clip(n, spec["min"], spec["max"])
                           .astype(np.int64))


def due_times(traffic: dict, count: int, seconds: float, rng) -> np.ndarray:
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    gaps = -np.log1p(-_quantiles(count))
    gaps = rng.permutation(gaps * seconds / gaps.sum())
    return np.cumsum(gaps) - gaps[0] * rng.random()


def kernel_draws(popularity: dict, count: int, n_kernels: int,
                 rng) -> np.ndarray:
    if popularity["dist"] != "zipf":
        raise ValueError(f"unknown popularity {popularity['dist']!r}")
    w = np.arange(1, n_kernels + 1, dtype=np.float64) ** -popularity["s"]
    ranks = np.repeat(np.arange(n_kernels), shares(w, count))
    ranking = np.random.default_rng(popularity["ranking_seed"]) \
        .permutation(n_kernels)
    return ranking[rng.permutation(ranks)]


def offsets(lens: np.ndarray, block_len: int, rng) -> np.ndarray:
    return (rng.random(len(lens)) * (block_len - lens + 1)).astype(np.int64)


def open_schedule(traffic: dict, n_kernels: int, n_tenants: int,
                  block_len: int, seconds: float, rng) -> list[Request]:
    """The requests due in ``seconds`` of the mix, by due time."""
    count = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    lens = lengths(traffic["length"], count, rng)
    due = due_times(traffic, count, seconds, rng)
    kern = kernel_draws(traffic["popularity"], count, n_kernels, rng)
    tenants = rng.permutation(np.arange(count) % n_tenants)
    offs = offsets(lens, block_len, rng)
    return [Request(kernel=int(k), tenant=f"tenant{t}", length=int(n),
                    offset=int(o), due=float(d))
            for k, t, n, o, d in zip(kern, tenants, lens, offs, due)]
