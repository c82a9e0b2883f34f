"""Open loop: requests fall due at Poisson arrivals, whatever the server does.

Spec keys: ``rate_rps``; ``prompt`` ({frame_tokens, text_tokens, frames:
[k...], frame_probs: [p...]}); ``output_tokens`` ({median, sigma, min,
max}: log-normal, rounded and clipped); ``base_seed``.

Every seed gets the same multiset of gaps and of sizes, drawn once from
``base_seed`` for the window's length; ``--seed`` only shuffles their order
and draws the token ids. The gaps are scaled to end just inside the
window, so every order puts the same requests in it: two seeds offer the
same work, in another order, and a seed cannot make a run heavier or
lighter.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from bench.traffic.common import Item, prompt_length, prompt_tokens, seed_words


class PoissonOpenLoop:
    def __init__(self, spec: dict, seed: int, vocab: int, seconds: float):
        rate = float(spec["rate_rps"])
        if rate <= 0:
            raise ValueError(f"rate_rps must be > 0, got {rate}")
        prompt, out = spec["prompt"], spec["output_tokens"]
        self.lengths = sorted({prompt_length(prompt, k) for k in prompt["frames"]})
        n = max(1, int(round(rate * seconds)))
        base = np.random.default_rng(int(spec["base_seed"]))
        gaps = base.exponential(1.0 / rate, n)
        gaps *= seconds * n / (n + 1) / gaps.sum()  # the last one due inside
        frames = base.choice(prompt["frames"], n, p=prompt["frame_probs"])
        outs = np.clip(np.rint(float(out["median"]) * np.exp(
            float(out["sigma"]) * base.standard_normal(n))),
            int(out["min"]), int(out["max"])).astype(int)
        order = np.random.default_rng(seed_words(seed) + [0x0A])
        gaps = gaps[order.permutation(n)]
        sizes = order.permutation(n)
        due = np.cumsum(gaps)
        self.items = [
            Item(rid, prompt_tokens(seed, rid, prompt_length(prompt, int(frames[j])),
                                    vocab), int(outs[j]), float(due[rid]))
            for rid, j in enumerate(sizes)
        ]
        self.seed, self.vocab = seed, vocab
        self.round_out = int(out["min"])
        self._pos = 0

    def warmup(self) -> List[Item]:
        return [Item(-1 - i, prompt_tokens(self.seed, (1 << 30) + i, n, self.vocab),
                     self.round_out)
                for i, n in enumerate(self.lengths)]

    def poll(self, t_rel: float) -> List[Item]:
        start = self._pos
        while self._pos < len(self.items) and self.items[self._pos].due_rel <= t_rel:
            self._pos += 1
        return self.items[start:self._pos]

    def finished(self, item: Item, t_rel: float) -> None:
        pass

    def next_due(self) -> Optional[float]:
        if self._pos < len(self.items):
            return self.items[self._pos].due_rel
        return None

    def close(self) -> None:
        self._pos = len(self.items)


def make_source(spec: dict, seed: int, vocab: int, seconds: float) -> PoissonOpenLoop:
    return PoissonOpenLoop(spec, seed, vocab, seconds)
