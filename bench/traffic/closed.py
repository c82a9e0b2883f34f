"""Closed loop: each client sends its next request when its last one ends.

Spec keys: ``clients``, ``prompt`` ({frame_tokens, text_tokens, frames}:
every prompt has ``frames`` frames), ``output_tokens``. Every request has
the same sizes, so a seed changes only the token ids.
"""
from __future__ import annotations

from typing import List, Optional

from bench.traffic.common import Item, prompt_length, prompt_tokens


class ClosedLoop:
    def __init__(self, spec: dict, seed: int, vocab: int, seconds: float):
        self.clients = int(spec["clients"])
        self.length = prompt_length(spec["prompt"], int(spec["prompt"]["frames"]))
        self.out_len = int(spec["output_tokens"])
        self.seed, self.vocab = seed, vocab
        self._next_rid = 0
        self._ready: List[Item] = []
        self._closed = False
        for _ in range(self.clients):
            self._ready.append(self._make(0.0))

    def _make(self, due_rel: float) -> Item:
        rid = self._next_rid
        self._next_rid += 1
        return Item(rid, prompt_tokens(self.seed, rid, self.length, self.vocab),
                    self.out_len, due_rel)

    def warmup(self) -> List[Item]:
        # rids far above the window's, so warm-up prompts never repeat one
        return [Item(-1, prompt_tokens(self.seed, 1 << 30, self.length, self.vocab),
                     2)]

    def poll(self, t_rel: float) -> List[Item]:
        due = [it for it in self._ready if it.due_rel <= t_rel]
        self._ready = [it for it in self._ready if it.due_rel > t_rel]
        return due

    def finished(self, item: Item, t_rel: float) -> None:
        if not self._closed:
            self._ready.append(self._make(t_rel))

    def next_due(self) -> Optional[float]:
        return min((it.due_rel for it in self._ready), default=None)

    def close(self) -> None:
        self._closed = True
        self._ready = []


def make_source(spec: dict, seed: int, vocab: int, seconds: float) -> ClosedLoop:
    return ClosedLoop(spec, seed, vocab, seconds)
