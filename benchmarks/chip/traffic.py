"""Open-loop traffic from a mix file (``traffic/<mix>.json``) and a seed.

Offered work is the same for every seed; the seed draws only the token
ids:

- a window of ``seconds`` at rate ``rate`` holds exactly
  N = round(rate * seconds) requests;
- their gaps are the N quantiles of an exponential distribution at
  (i + 1/2) / N, scaled so the N arrivals fall inside the window (a
  Poisson process's gaps);
- prompt and output lengths are the N quantiles at (i + 1/2) / N of the
  mix's length distributions (``_quantile_lengths``): a fixed length, a
  table of published quantiles, or a lognormal;
- gaps, prompt lengths and output lengths are each given in an order
  shuffled once, the same for every seed. A seed-drawn order changed how
  much of the long-tailed work fell inside the window: chat runs of
  different seeds read 143 to 178 output tokens/s where runs of one
  order repeat (``PERF.md``).

A lead-in of ``lead_in_s`` seconds before the window is built the same
way, so occupancy is steady when the window opens. A ``backlog`` mix
queues all of its requests before the window instead (``due`` 0).

Every request's ids are built here, during set-up.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Offered:
    """One request as offered: due time (seconds from the window's
    start; negative in the lead-in), prompt ids and output length."""
    due: float
    prompt: np.ndarray
    out_len: int
    in_window: bool


def load_mix(name: str, root: Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def _quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """N quantiles at (i + 1/2) / N of a length distribution, rounded:

    - ``{"fixed": L}``: every request has length L;
    - ``{"quantiles": [[q, L], ...]}``: a table of quantiles (q from 0 to 1,
      ascending), read between its points by straight lines;
    - ``{"median", "sigma", "min", "max"}``: a lognormal, clipped.
    """
    ps = [(i + 0.5) / n for i in range(n)]
    if "fixed" in dist:
        q = [dist["fixed"]] * n
    elif "quantiles" in dist:
        qs, ls = zip(*dist["quantiles"])
        if list(qs) != sorted(qs) or qs[0] != 0.0 or qs[-1] != 1.0:
            raise ValueError("quantiles run from 0 to 1, ascending")
        q = np.interp(ps, qs, ls)
    else:
        nd = NormalDist(math.log(dist["median"]), dist["sigma"])
        q = np.clip([math.exp(nd.inv_cdf(x)) for x in ps], dist["min"],
                    dist["max"])
    return np.rint(q).astype(np.int64)


def _gaps(n: int, span: float) -> np.ndarray:
    """N exponential quantiles scaled to sum to ``span`` (ascending)."""
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (span / g.sum())


def _order(values: np.ndarray, which: int) -> np.ndarray:
    """``values`` in a fixed shuffled order (one per use, never the
    seed's)."""
    return np.random.default_rng(which).permutation(values)


def _requests(mix: dict, n: int, rng, vocab: int) -> tuple:
    p = _order(_quantile_lengths(mix["prompt"], n), 1)
    o = _order(_quantile_lengths(mix["output"], n), 2)
    ids = [rng.integers(1, vocab, int(k), dtype=np.int32) for k in p]
    return ids, o


def offered(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The lead-in and window requests of one run, in due order."""
    rng = np.random.default_rng(seed)
    out = []
    if mix["arrivals"] == "backlog":
        n = mix["backlog_requests"]
        ids, o = _requests(mix, n, rng, vocab)
        return [Offered(0.0, a, int(b), True) for a, b in zip(ids, o)]
    rate = mix["rate"]
    for start, span, in_window in ((-mix["lead_in_s"], mix["lead_in_s"],
                                    False), (0.0, seconds, True)):
        n = round(rate * span)
        if n == 0:
            continue
        ids, o = _requests(mix, n, rng, vocab)
        gaps = _order(_gaps(n, span), 3)
        # Each arrival at the middle of its gap: all N fall strictly
        # inside [start, start + span).
        due = start + np.cumsum(gaps) - gaps / 2
        out += [Offered(float(t), a, int(b), in_window)
                for t, a, b in zip(due, ids, o)]
    return out


def padded(n: int, step: int) -> int:
    """Prompt length as the engine pads it (``bucket_policy`` step:K)."""
    return -(-n // step) * step
