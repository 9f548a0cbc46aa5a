"""The one traffic generator: reads a mix's data file and makes its requests.

A mix is a JSON file under ``bench/traffic/`` and holds parameters only.
Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps, each a fixed grid of quantiles of its stated distribution;
the seed only permutes them and draws the token ids. In an open loop the
requests due in the window are one such multiset, with their gaps scaled
to fill the window exactly, and the requests due after it another. So two
seeds do the same amount of work in the window in another order, and the
spread between seeds is the spread of the system, not of the draw.

Kinds of mix:

* ``open``: requests arrive on a schedule, whether or not earlier ones are
  done, with Poisson gaps at the mean rate ``rate_rps``.
* ``closed``: ``clients`` callers, each sending its next request when the
  last one finished.

A length distribution is ``{"dist": "lognormal", "median", "sigma", "min",
"max", "round_to"}``: quantiles of the lognormal, clipped to [min, max],
rounded up to a multiple of ``round_to``.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""

    due_s: float            # offset from the window's opening (open loop)
    client: int             # caller index (closed loop); -1 in an open loop
    prompt: np.ndarray      # (P,) int32 token ids
    max_new_tokens: int


def load(name: str, root: Path = TRAFFIC_DIR) -> Dict:
    return json.loads((root / f"{name}.json").read_text())


def length_grid(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist()
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    vals = np.array([math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n))
                     for i in range(n)])
    vals = np.clip(np.rint(vals), dist["min"], dist["max"])
    r = int(dist.get("round_to", 1))
    vals = np.ceil(vals / r) * r
    return np.clip(vals, dist["min"], dist["max"]).astype(np.int64)


def gap_grid(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the quantiles (i + 0.5) / n:
    a Poisson process's gaps, with the draw taken out."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def n_requests(mix: Dict, seconds: float) -> int:
    """How many requests the schedule holds: ``rate * seconds`` due in the
    window and ``rate * tail_s`` after it, or ``per_client`` per caller in a
    closed loop."""
    if mix["kind"] == "closed":
        return int(mix["clients"] * mix["per_client"])
    return sum(_blocks(mix, seconds))


def _blocks(mix: Dict, seconds: float):
    """Requests due in the window, and after it."""
    r = mix["rate_rps"]
    return (max(1, int(round(r * seconds))),
            int(math.ceil(r * mix["tail_s"])))


def _arrivals(rng, n: int, lo: float, hi: float) -> np.ndarray:
    """``n`` arrivals in [lo, hi): exponential gaps at fixed quantiles, in
    the seed's order, scaled to fill the span exactly."""
    g = rng.permutation(gap_grid(1.0, n))
    g = g / g.sum() * (hi - lo)
    return lo + np.concatenate([[0.0], np.cumsum(g[:-1])])


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The requests of one run, in due order (open) or client order
    (closed). Determined by ``seed``; the sizes and gaps are the same
    multiset for every seed."""
    rng = np.random.default_rng(seed)
    if mix["kind"] == "closed":
        n = n_requests(mix, seconds)
        plen = rng.permutation(length_grid(mix["prompt"], n))
        olen = rng.permutation(length_grid(mix["output"], n))
        due = np.zeros(n)
        client = np.arange(n) % mix["clients"]
    elif mix["kind"] == "open":
        # the window's requests and the ones after it are two blocks, each
        # its own fixed multiset of lengths and gaps
        plen, olen, due = [], [], []
        lo = 0.0
        for n, hi in zip(_blocks(mix, seconds),
                         (seconds, seconds + mix["tail_s"])):
            if n == 0:
                continue
            plen.append(rng.permutation(length_grid(mix["prompt"], n)))
            olen.append(rng.permutation(length_grid(mix["output"], n)))
            due.append(_arrivals(rng, n, lo, hi))
            lo = hi
        plen, olen, due = (np.concatenate(x) for x in (plen, olen, due))
        n = len(due)
        client = np.full(n, -1)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return [Item(float(due[i]), int(client[i]),
                 rng.integers(0, vocab, int(plen[i])).astype(np.int32),
                 int(olen[i]))
            for i in range(n)]
