"""Seeded traffic from a data file: one general generator for every mix.

A mix (``chipbench/traffic/<name>.json``) holds only parameters:

* ``loop``: ``"closed"`` (each client sends its next request when its last
  one finished) or ``"open"`` (Poisson arrivals at ``rate_per_s``, due
  whether or not the server keeps up);
* ``models``: per model either ``{"clients": n}`` (n clients, each
  sending its next request when its last one finished, in either loop)
  or ``{"share": f}`` (open loop: the model's share of the arrivals);
* ``prompt`` / ``output``: a length distribution, ``{"dist": "uniform",
  "min", "max"}`` or ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}``, both clipped to ``[min, max]``;
* ``levels``: how many quantiles of each distribution the mix uses;
* ``warm_batch``: the most prompts of one model and prefill bucket that
  set-up warms as one coalesced pass;
* ``check_requests``: finished requests per model compared with the
  reference after the window.

Every seed gets the same multiset of sizes and, in the open loop, the same
multiset of inter-arrival gaps; the seed only orders them, pairs prompt
with output lengths and draws the prompt token ids.  So two seeds do the
same work in another order, and the prompt lengths the window's passes
hold are known before it opens.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantile_levels(spec: Dict, levels: int) -> List[int]:
    """The ``levels`` mid-quantiles of a clipped length distribution, as
    whole token counts (ascending, repeats kept)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    qs = [(i + 0.5) / levels for i in range(levels)]
    if spec["dist"] == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    elif spec["dist"] == "lognormal":
        mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
        vals = [math.exp(mu + sigma * NormalDist().inv_cdf(q)) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [min(hi, max(lo, int(round(v)))) for v in vals]


@dataclass
class Arrival:
    """One request as the generator makes it."""

    model: str
    prompt_len: int
    output_len: int
    due: float = 0.0          # seconds after the window opens (open loop)
    client: int = -1          # closed loop: which client sends it


@dataclass
class Traffic:
    name: str
    loop: str
    models: Dict[str, Dict]
    prompt: Dict
    output: Dict
    levels: int
    rate_per_s: float = 0.0
    warm_batch: int = 1
    check_requests: int = 4
    max_ctx: int = 1024
    raw: Dict = field(default_factory=dict)

    @classmethod
    def from_file(cls, path) -> "Traffic":
        raw = json.loads(Path(path).read_text())
        t = cls(name=Path(path).stem, loop=raw["loop"], models=raw["models"],
                prompt=raw["prompt"], output=raw["output"],
                levels=int(raw["levels"]),
                rate_per_s=float(raw.get("rate_per_s", 0.0)),
                warm_batch=int(raw.get("warm_batch", 1)),
                check_requests=int(raw.get("check_requests", 4)),
                max_ctx=int(raw.get("max_ctx", 1024)), raw=raw)
        t.validate()
        return t

    def validate(self) -> None:
        if self.loop not in ("closed", "open"):
            raise ValueError(f"{self.name}: loop must be closed or open")
        for m, spec in self.models.items():
            if ("clients" in spec) == ("share" in spec) or (
                    self.loop == "closed" and "share" in spec):
                raise ValueError(f"{self.name}: {m} needs 'clients' or, in "
                                 f"an open loop, 'share'")
        if self.loop == "open" and (self.rate_per_s <= 0
                                    or not self.open_models()):
            raise ValueError(f"{self.name}: open loop needs rate_per_s > 0 "
                             f"and a model with a share")
        if max(self.prompt_levels()) + max(self.output_levels()) \
                > self.max_ctx:
            raise ValueError(f"{self.name}: prompt + output can pass "
                             f"max_ctx {self.max_ctx}")

    def open_models(self) -> List[str]:
        return [m for m, s in self.models.items() if "share" in s]

    def client_models(self) -> Dict[str, int]:
        return {m: int(s["clients"]) for m, s in self.models.items()
                if "clients" in s}

    def prompt_levels(self) -> List[int]:
        return quantile_levels(self.prompt, self.levels)

    def output_levels(self) -> List[int]:
        return quantile_levels(self.output, self.levels)

    # ------------------------------------------------------------------
    def _pairs(self, rng: np.random.Generator, n: int) -> List[tuple]:
        """``n`` (prompt, output) pairs: ``n // levels`` whole passes over
        the levels plus evenly spaced levels for the rest, paired and
        ordered by the seed."""
        p, o = self.prompt_levels(), self.output_levels()
        L = len(p)
        ps = p * (n // L) + [p[(i * L) // (n % L)] for i in range(n % L)]
        os_ = o * (n // L) + [o[(i * L + L // 2) // (n % L) % L]
                              for i in range(n % L)]
        # the multisets of prompt and output lengths are fixed by n; the
        # seed pairs them and orders the pairs
        os_ = [os_[i] for i in rng.permutation(n)]
        return [(ps[i], os_[i]) for i in rng.permutation(n)]

    def closed_queues(self, seed: int, per_model: int
                      ) -> Dict[str, List[Arrival]]:
        """Per model with clients: the order in which its clients draw
        their requests (the first ``clients`` fill the slots)."""
        rng = np.random.default_rng([seed, 1])
        return {m: [Arrival(m, a, b) for a, b in self._pairs(rng, per_model)]
                for m in self.client_models()}

    def open_schedule(self, seed: int, seconds: float) -> List[Arrival]:
        """Open loop: ``round(rate * seconds)`` arrivals due inside the
        window.  The gaps are the mid-quantiles of the exponential
        distribution, rescaled to fill the window, in a seeded order."""
        rng = np.random.default_rng([seed, 2])
        n = max(1, int(round(self.rate_per_s * seconds)))
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        gaps *= seconds / gaps.sum()
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
        names = self.open_models()
        shares = np.array([float(self.models[m]["share"]) for m in names])
        counts = np.floor(shares / shares.sum() * n).astype(int)
        for i in np.argsort(-shares)[: n - counts.sum()]:
            counts[i] += 1
        who = np.repeat(np.arange(len(names)), counts)[rng.permutation(n)]
        pairs = {m: iter(self._pairs(rng, int(c)))
                 for m, c in zip(names, counts)}
        out = []
        for t, k in zip(due, who):
            a, b = next(pairs[names[k]])
            out.append(Arrival(names[k], a, b, due=float(t)))
        return out


def prompt_ids(seed: int, request_index: int, length: int,
               vocab: int) -> np.ndarray:
    """Prompt token ids of one request, from the seed alone."""
    rng = np.random.default_rng([seed, 3, request_index])
    return rng.integers(0, vocab, length, dtype=np.int64).astype(np.int32)


def key_from_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` from any whole seed."""
    return int(np.random.default_rng([seed, 4, salt]).integers(0, 2**31 - 1))
