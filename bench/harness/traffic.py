"""The one traffic generator: every mix is a data file that it reads.

Sizes come from fixed quantiles of the mix's distributions, so every seed
gets the same set of sizes; the seed only rotates which request gets
which (a Cranley-Patterson shift of a van der Corput sequence, which
keeps every prefix of the sequence stratified) and draws the token ids.

Serving mixes (``"driver": "serve"``):

  prompt_tokens  {median, sigma, min, max, menu}: log-normal, clipped,
                 then rounded to a menu of ``menu`` lengths at the
                 distribution's quantiles (each prompt length compiles a
                 prefill shape, so the menu is what set-up warms)
  output_tokens  {median, sigma, min, max}: log-normal, clipped
  clients_per_slot  a closed loop of ``clients_per_slot * n_slots``
                 clients, each sending its next request when the last
                 one finishes; the loop starts from its steady mix
                 (:meth:`ServeMix.steady_population`)
  pool           the engine's ``PoolConfig``

Training mixes (``"driver": "train"``): ``batch`` rows of ``seq_len``
tokens per step, drawn Zipf(``zipf_a``) as ``data/pipeline.TokenPipeline``
draws them, one fresh batch per step.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _radical_inverse(i: int, base: int) -> float:
    f, r = 1.0, 0.0
    while i:
        f /= base
        r += f * (i % base)
        i //= base
    return r


def _shift(seed: int, stream: int) -> float:
    return float(np.random.default_rng([seed, stream]).random())


def stratified(j: int, seed: int, base: int) -> float:
    """The j-th point in (0, 1) of the seed's shifted sequence in ``base``."""
    q = (_radical_inverse(j + 1, base) + _shift(seed, base)) % 1.0
    return min(max(q, 1e-9), 1.0 - 1e-9)


def lognormal_tokens(spec: dict, q: float) -> int:
    x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(q))
    return int(min(max(round(x), spec["min"]), spec["max"]))


def prompt_menu(spec: dict) -> list[int]:
    """The fixed lengths prompts are rounded to: quantiles at the middle
    of ``menu`` equal-probability bins."""
    n = spec["menu"]
    return [lognormal_tokens(spec, (i + 0.5) / n) for i in range(n)]


class ServeMix:
    """Requests of a serving mix for one seed, made on demand by index."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t = traffic
        self.seed = seed
        self.vocab = vocab
        self.menu = prompt_menu(traffic["prompt_tokens"])

    def sizes(self, j: int) -> tuple[int, int]:
        """(prompt tokens, output tokens) of request ``j``."""
        qp = stratified(j, self.seed, 2)
        qo = stratified(j, self.seed, 3)
        prompt = self.menu[int(qp * len(self.menu))]
        return prompt, lognormal_tokens(self.t["output_tokens"], qo)

    def prompt(self, j: int, n: int, stream: int = 7) -> list[int]:
        rng = np.random.default_rng([self.seed, stream, j])
        return rng.integers(0, self.vocab, n).tolist()

    def steady_population(self, n: int, grid: int = 4096
                          ) -> list[tuple[int, int, int]]:
        """(prompt tokens, output tokens, tokens already served) of the
        ``n`` requests that a closed loop's ``n`` busy slots hold once it
        runs steadily.  A slot holds a request for as long as it
        generates, so its odds go with its output length (the length
        bias of a renewal process), at a progress uniform over it.  The
        served part is rounded down so that prompt and served part fill
        whole pages, which keeps the prefill shapes of this population
        few.  The set is the same for every seed; the seed only orders
        it."""
        spec = self.t["output_tokens"]
        page = self.t["pool"]["page_size"]
        outs = np.array([lognormal_tokens(spec, (g + 0.5) / grid)
                         for g in range(grid)])
        cdf = np.cumsum(outs) / outs.sum()
        pop = []
        for i in range(n):
            o = int(outs[min(np.searchsorted(cdf, (i + 0.5) / n),
                             grid - 1)])
            p = self.menu[int(_radical_inverse(i + 1, 2) * len(self.menu))]
            k = int(_radical_inverse(i + 1, 3) * o)
            k = max(0, (p + k) // page * page - p)
            pop.append((p, o, k))
        order = np.random.default_rng([self.seed, 13]).permutation(n)
        return [pop[i] for i in order]

    def warmup_prompts(self) -> list[list[int]]:
        """One prompt of every menu length (tokens from the seed)."""
        return [self.prompt(i, n, stream=8)
                for i, n in enumerate(sorted(set(self.menu)))]


def train_batch(traffic: dict, seed: int, step: int, vocab: int) -> dict:
    """The rows of one training step: Zipf tokens, labels the next token."""
    rng = np.random.default_rng((seed, step, 0))
    toks = rng.zipf(traffic["zipf_a"],
                    size=(traffic["batch"], traffic["seq_len"] + 1))
    toks = np.minimum(toks - 1, vocab - 1).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
