"""The one traffic generator: reads a mix's parameters from its data file.

Every seed serves the same request sizes in another order.  The stream
is made of blocks of ``block`` requests; in each block the prompt lengths
are the mix's length classes in their exact proportions, and the output
lengths are the ``block`` quantiles of the mix's log-normal distribution,
clipped.  The seed shuffles both lists of each block independently (so it
changes which prompt meets which output length and the order requests are
sent in) and draws the prompt token ids.  That keeps the work a run
offers, from its first request on, the same from seed to seed.

A closed-loop mix (``"loop": "closed"``) has a client per batch slot, each
sending its next request when its previous one finishes.  With
``"start": "steady"`` its first wave, one request per client, starts the
loop in its steady state: the output
lengths of the wave are the quantiles of the residual life of the mix's
output lengths (what a request caught mid-flight at a random step still
has to generate), so about ``clients / mean output`` requests finish in
every step from the first on, as in a loop that has long been running,
and not all at once after a whole lifetime.  Without it the first wave is
the first block.  An open-loop mix
(``"loop": "open"``) sends at the seeded Poisson times of
``rate_per_s``, whatever the engine's progress.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt_len: int
    max_new: int


def class_counts(classes: Dict[str, float], n: int) -> Dict[int, int]:
    """Split ``n`` requests over the prompt-length classes in proportion to
    their weights (largest remainders get the leftover requests)."""
    lens = sorted(int(k) for k in classes)
    total = sum(classes[str(k)] for k in lens)
    exact = [classes[str(k)] / total * n for k in lens]
    counts = [int(x) for x in exact]
    order = sorted(range(len(lens)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return dict(zip(lens, counts))


def output_lengths(out: dict, n: int) -> List[int]:
    """The ``n`` quantiles (i + 0.5) / n of the log-normal output-length
    distribution, rounded and clipped to [min, max]."""
    if out["dist"] != "lognormal":
        raise ValueError(f"unknown output distribution {out['dist']!r}")
    nd = NormalDist()
    mu, sigma = np.log(out["median"]), out["sigma"]
    q = [float(np.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)))
         for i in range(n)]
    return [int(min(max(round(x), out["min"]), out["max"])) for x in q]


def residual_lengths(lives: List[int], n: int) -> List[int]:
    """The ``n`` quantiles (i + 0.5) / n of the tokens a request still has
    to generate at a random step of a loop whose requests generate
    ``lives`` tokens each, equally often: P(r) is proportional to the share
    of ``lives`` of at least r.  At least 2, since the engine serves a
    request of one token a second one."""
    lives = np.sort(np.asarray(lives))
    r = np.arange(1, int(lives[-1]) + 1)
    surv = len(lives) - np.searchsorted(lives, r, side="left")
    cdf = np.cumsum(surv) / surv.sum()
    q = (np.arange(n) + 0.5) / n
    return [max(int(x) + 1, 2) for x in np.searchsorted(cdf, q)]


class Traffic:
    """The request stream of one run: sizes from the mix, order and token
    ids from the seed.  A closed loop of ``clients`` clients that starts
    steady opens with a first wave of ``clients`` requests."""

    def __init__(self, mix: dict, seed: int, vocab: int, clients: int = 0):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        n, blocks = int(mix["block"]), int(mix["blocks"])
        rng = np.random.default_rng([self.seed, 0])
        outs = output_lengths(mix["output"], n)
        self.specs = []
        if clients and self.steady_start:
            self._add_block(rng, clients, residual_lengths(outs, clients))
        for _ in range(blocks):
            self._add_block(rng, n, outs)
        self._next = 0

    def _add_block(self, rng, n: int, outs: List[int]):
        """``n`` requests: the prompt-length classes in their proportions
        and the output lengths ``outs``, each list in the seed's order."""
        prompts = [L for L, c in
                   class_counts(self.mix["prompt_classes"], n).items()
                   for _ in range(c)]
        pairs = zip([prompts[i] for i in rng.permutation(n)],
                    [outs[i] for i in rng.permutation(n)])
        self.specs += [RequestSpec(len(self.specs) + i, p, o)
                       for i, (p, o) in enumerate(pairs)]

    @property
    def prompt_lengths(self) -> List[int]:
        """Every prompt length the mix can send (the prefill shapes that
        set-up warms)."""
        return sorted(int(k) for k in self.mix["prompt_classes"])

    def tokens(self, spec: RequestSpec) -> List[int]:
        """Prompt token ids, uniform over the vocabulary."""
        rng = np.random.default_rng([self.seed, 1, spec.index])
        return rng.integers(0, self.vocab, size=spec.prompt_len).tolist()

    @property
    def open_loop(self) -> bool:
        return self.mix.get("loop", "closed") == "open"

    @property
    def steady_start(self) -> bool:
        return not self.open_loop and self.mix.get("start") == "steady"

    def send_times(self, horizon_s: float) -> List[float]:
        """Seconds after the window opens at which an open-loop mix sends,
        up to ``horizon_s``: seeded exponential gaps at ``rate_per_s``."""
        rng = np.random.default_rng([self.seed, 2])
        rate, t, out = float(self.mix["rate_per_s"]), 0.0, []
        while True:
            t += rng.exponential(1.0 / rate)
            if t > horizon_s:
                return out
            out.append(t)

    def next(self) -> RequestSpec:
        """The next request of the stream (the pool wraps round)."""
        spec = self.specs[self._next % len(self.specs)]
        self._next += 1
        if self._next > len(self.specs):
            spec = RequestSpec(self._next - 1, spec.prompt_len, spec.max_new)
        return spec
