"""Faults planted under the timed path, to see the correctness check catch
them: ``hooks`` for ``harness.run_cell``. Used by the tests and by
``bench/control.py --fault``; the benchmark's own runs never plant one.

- ``stale_state``: a decode step returns the KV cache it was given;
- ``altered_token``: one slot's token is changed where it is produced;
- ``half_batch``: the importance that selection ranks is the mean over
  half of the batch's rows, the rest left out.
"""
from __future__ import annotations

import contextlib
import importlib


def _wrap_decode(change):
    def hook(prog):
        eng = prog.engine
        orig = eng._decode_scan

        def decode(params, tokens, cache, n, plan):
            return change(orig(params, tokens, cache, n, plan), cache)

        eng._decode_scan = decode
    return hook


def stale_state():
    return _wrap_decode(lambda out, cache: (out[0], cache) + tuple(out[2:]))


def altered_token(vocab: int):
    def change(out, cache):
        toks = out[0]
        return (toks.at[0, -1].set((toks[0, -1] + 1) % vocab),) + tuple(out[1:])
    return _wrap_decode(change)


@contextlib.contextmanager
def half_batch():
    """While active, selection's importance averages half of the rows."""
    import jax.numpy as jnp

    # the module: ``repro.core`` re-exports a function of the same name
    mod = importlib.import_module("repro.core.importance")
    orig = mod.importance

    def half(acts):
        a = jnp.abs(acts.astype(jnp.float32)).reshape(-1, acts.shape[-1])
        return a[: max(1, a.shape[0] // 2)].mean(axis=0)

    mod.importance = half
    try:
        yield
    finally:
        mod.importance = orig


FAULTS = ("stale_state", "altered_token", "half_batch")
