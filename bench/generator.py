"""The one traffic generator: a mix's data file plus a seed → requests.

A mix (``bench/traffic/<name>.json``) gives the loop type, the rate or the
number of clients, and the prompt and output length distributions: each a
lognormal whose median is the published one (``published_median``, or
``published_mean`` converted by the lognormal's ``sigma``) times the mix's
one ``length_scale``, so the cut to the served positions keeps the
source's ratio of prompt to answer; then clipped to ``[min, max]``. Every
seed gets the same multiset of lengths and arrival gaps (quantiles of the
stated distributions); the seed only draws their order and the prompt
tokens. So two seeds offer the same work, and runs differ by order alone.

Open loop: arrivals on a schedule from ``-preroll_s`` to the end of the
window, whether or not earlier requests have finished; a request's latency
runs from its scheduled send. Closed loop: each client sends its next
request when the previous one returns.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# lengths per client in a closed-loop pool; a client that uses up its
# share reuses the pool's lengths, with fresh tokens
CLOSED_POOL_PER_CLIENT = 64
PAIRING_SEED = 0x5EED


@dataclass(frozen=True)
class Req:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    send_at: Optional[float]    # open loop: seconds after the pre-roll began


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *salt])


def median_of(spec: dict, scale: float) -> float:
    """The served median: the published median (or the median of a
    lognormal with the published mean and ``sigma``) times ``scale``."""
    if "published_median" in spec:
        return scale * spec["published_median"]
    return scale * spec["published_mean"] * math.exp(-spec["sigma"] ** 2 / 2)


def length_set(spec: dict, n: int, scale: float) -> np.ndarray:
    """n lengths at the (i + 0.5)/n quantiles of a lognormal with the
    scaled median and sigma, rounded and clipped to [min, max]."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    med = median_of(spec, scale)
    vals = [med * math.exp(spec["sigma"] * nd.inv_cdf(x)) for x in q]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps at the quantiles of an exponential of ``rate``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop type {self.loop!r}")
        self.clients = int(mix["clients"])
        self.preroll_s = float(mix["preroll_s"])
        self.seconds = float(seconds)
        if self.loop == "open":
            self.rate = float(mix["rate_per_s"])
            n = max(1, round(self.rate * (self.preroll_s + self.seconds)))
        else:
            self.rate = None
            n = self.clients * CLOSED_POOL_PER_CLIENT
        # (prompt, output) pairs are matched by a permutation fixed for
        # the mix, not the seed; the seed draws only their order
        scale = float(mix["length_scale"])
        prompts = length_set(mix["prompt_tokens"], n, scale)
        outs = np.random.default_rng(PAIRING_SEED).permutation(
            length_set(mix["output_tokens"], n, scale))
        outs = np.minimum(outs, mix["max_total_tokens"] - prompts)
        if outs.min() < 1:
            raise ValueError("max_total_tokens leaves a prompt no output")
        order = _rng(seed, 0)
        idx = order.permutation(n)
        self._lengths = list(zip(prompts[idx].tolist(), outs[idx].tolist()))
        if self.loop == "open":
            self._send_at = np.cumsum(order.permutation(exp_gaps(self.rate, n)))
        self._cache = {}

    def request(self, i: int) -> Req:
        """Request ``i``: the lengths of pool entry ``i mod n``, tokens of
        its own, so no two requests of a run share a prompt."""
        if i not in self._cache:
            n = len(self._lengths)
            plen, out = self._lengths[i % n]
            toks = _rng(self.seed, 1, i).integers(0, self.vocab, size=plen,
                                                  dtype=np.int32)
            send = float(self._send_at[i]) if self.loop == "open" else None
            self._cache[i] = Req(i, toks, int(out), send)
        return self._cache[i]

    def schedule(self) -> List[Req]:
        """Open loop: every request due before the window ends, in order."""
        end = self.preroll_s + self.seconds
        return [self.request(i) for i in range(len(self._lengths))
                if self._send_at[i] < end]

    def client_requests(self, c: int):
        """Closed loop: client ``c``'s endless request sequence."""
        i = c
        while True:
            yield self.request(i)
            i += self.clients
