"""What every traffic generator shares: the request record and seeded prompts.

A traffic mix is a JSON file beside this module (``<mix>.json``) whose
``kind`` names the generator module (``<kind>.py``) that reads it. Each
generator module defines ``make_source(spec, seed, vocab, seconds)``
returning an object with the methods of :class:`Source`.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the traffic makes it: prompt token ids, how many
    tokens to generate, and when it falls due (seconds after the window
    opens; warm-up items are due at once)."""

    rid: int
    tokens: np.ndarray
    out_len: int
    due_rel: float = 0.0


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words, for ``np.random.default_rng``."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = [seed & 0xFFFFFFFF]
    seed >>= 32
    while seed:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
    return words


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``rid``: a pure function of (seed, rid)."""
    rng = np.random.default_rng(seed_words(seed) + [rid, 0x70])
    return rng.integers(0, vocab, length, dtype=np.int32)


def prompt_length(prompt: dict, frames: int) -> int:
    """Frames of ``frame_tokens`` visual tokens, then ``text_tokens``."""
    return frames * int(prompt["frame_tokens"]) + int(prompt["text_tokens"])


class Source:
    """The interface the harness drives (documentation only)."""

    def warmup(self) -> List[Item]:
        """One short request per prompt shape the mix will send."""
        raise NotImplementedError

    def poll(self, t_rel: float) -> List[Item]:
        """Items due at or before ``t_rel`` not handed out yet."""
        raise NotImplementedError

    def finished(self, item: Item, t_rel: float) -> None:
        """``item`` delivered its last token at ``t_rel``."""
        raise NotImplementedError

    def next_due(self) -> Optional[float]:
        """When the next item falls due, or None if none is scheduled."""
        raise NotImplementedError

    def close(self) -> None:
        """The window has closed: make no further items."""
        raise NotImplementedError
