"""The one traffic generator: every mix is a data file of parameters under
``bench/traffic/`` that this module reads.

A mix is drawn in blocks. The sizes of a block (prompt and output lengths,
lambda values and, for an open loop, the gaps between arrivals) are fixed
quantiles of the mix's distributions, the same for every seed; the seed
only orders each block, picks the client and task of each request and
draws its token ids and query embedding. So two seeds ask for the same
work in another order, and a window that spans several blocks sees the
same mix whatever the seed.

Query embeddings stand for the router's sentence encoder: each client
draws its tasks from a fixed Dirichlet mixture over ``tasks`` centres in
the encoder's space, and a query is its task's centre plus noise, unit
normalised.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Iterator

import numpy as np

#: seed of everything that is fixed across runs (centres, client mixtures,
#: the pairing of prompt and output quantiles)
BASE_SEED = 20260118


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray        #: token ids, int32
    max_new: int
    lam: float
    client: int
    x: np.ndarray             #: query embedding, float32 (d_emb,)
    due: float = 0.0          #: seconds after the window opens (open loop)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a log-normal length distribution: the
    quantiles at (i + 1/2) / n, clipped to [min, max], as ints."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def _block_sizes(mix: dict):
    """The fixed contents of one block: prompt lengths, output lengths,
    lambdas and unit-rate exponential gaps, index-aligned."""
    n = int(mix["block"])
    base = np.random.default_rng(BASE_SEED)
    prompts = quantiles(mix["prompt_tokens"], n)
    outputs = quantiles(mix["output_tokens"], n)[base.permutation(n)]
    lams = np.resize(np.asarray(mix["lambdas"], np.float64), n)
    lams = lams[base.permutation(n)]
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)[base.permutation(n)]
    return prompts, outputs, lams, gaps


def _space(mix: dict, d_emb: int):
    """Task centres (tasks, d_emb) and each client's task mixture."""
    base = np.random.default_rng(BASE_SEED + 1)
    centres = base.standard_normal((mix["tasks"], d_emb))
    centres /= np.linalg.norm(centres, axis=1, keepdims=True)
    mixes = base.dirichlet([mix["dirichlet_alpha"]] * mix["tasks"],
                           size=mix["clients"])
    return centres, mixes


def stream(mix: dict, seed: int, *, vocab: int, d_emb: int
           ) -> Iterator[Request]:
    """The mix's requests in order, without end. For an open loop each
    request carries the time it is due, at ``mix["rate_per_s"]``."""
    prompts, outputs, lams, gaps = _block_sizes(mix)
    centres, mixes = _space(mix, d_emb)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    rate = float(mix.get("rate_per_s") or 0.0)
    n, i, due = len(prompts), 0, 0.0
    while True:
        for j in rng.permutation(n):
            client = int(rng.integers(mix["clients"]))
            task = int(rng.choice(mix["tasks"], p=mixes[client]))
            x = centres[task] + 0.5 * rng.standard_normal(d_emb) / np.sqrt(
                d_emb)
            x = (x / np.linalg.norm(x)).astype(np.float32)
            toks = rng.integers(1, vocab, size=int(prompts[j]),
                                dtype=np.int32)
            if rate:
                due += gaps[j] / rate
            yield Request(i, toks, int(outputs[j]), float(lams[j]), client,
                          x, due)
            i += 1


class Evaluations:
    """Harvested evaluations for a sync mix: rows (x, model, outcome,
    cost) of each client. A client draws its tasks from its Dirichlet
    mixture and its model from a skewed coverage (Dirichlet over the pool,
    ``model_alpha``), as clients only see the models they were routed to;
    the outcome is Bernoulli in a fixed per-(task, model) accuracy and the
    cost is the model's cost per token times a spread in [0.5, 1.5)."""

    def __init__(self, mix: dict, seed: int, costs, d_emb: int):
        self.mix, self.costs, self.d_emb = mix, np.asarray(costs), d_emb
        self.centres, self.mixes = _space(mix, d_emb)
        base = np.random.default_rng(BASE_SEED + 2)
        M = len(costs)
        self.cover = base.dirichlet([mix["model_alpha"]] * M,
                                    size=mix["clients"])
        # the costlier model is the more accurate on most tasks
        self.acc = 1.0 / (1.0 + np.exp(-(base.standard_normal(
            (mix["tasks"], M)) + np.linspace(1.0, -0.5, M))))
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])

    def rows(self, client: int, n: int) -> dict:
        r, mix = self.rng, self.mix
        task = r.choice(mix["tasks"], size=n, p=self.mixes[client])
        x = self.centres[task] + 0.5 * r.standard_normal(
            (n, self.d_emb)) / np.sqrt(self.d_emb)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        m = r.choice(len(self.costs), size=n, p=self.cover[client])
        acc = (r.random(n) < self.acc[task, m]).astype(np.float32)
        cost = (self.costs[m] * (0.5 + r.random(n))).astype(np.float32)
        return {"x": x.astype(np.float32), "m": m.astype(np.int32),
                "acc": acc, "cost": cost}
