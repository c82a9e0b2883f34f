"""Whether what the timed path served is right: a comparison with the
plain float32 reference, over a sample of the requests the window finished.

Numbers compared (each against a limit in ``limits/<cell>.json``):

- ``logit_gap``: over every served token of the sample (the prefill's
  token, then each decoded one), the widest gap by which the reference's
  logit for the served token lies below the reference's best logit at that
  position. The reference reads each prompt with its served tokens, the
  prompt dense and each decoded position under the masks the selection
  chose for that step, so it covers the dense prefill, decoding through
  the cache and attention, the gather kernels and the tokens.
- ``kv_rel_err``: for the requests whose KV cache the program left intact
  (one full cohort of a sparse cell, one request of a dense cell), the
  largest relative error, over layers and the cache entries the reference
  names (``cache_entries``: keys and values, or one latent), of the cache
  against the reference's, over every position, prompt and decoded.
- ``kv0_rel_err``: the same at the first layer and the decoded positions
  only. Forty layers of bfloat16 rounding drift the deep layers by a few
  percent, which hides a step down in precision there; at the first layer a
  decoded position has been rounded a few times only, so a weight stored
  at 8 bits shows plainly.
- ``mask_mismatch`` (selecting methods): the selection itself. For a
  cohort of requests that filled every slot from admission to finish, the
  reference ranks each site's importance (mean |input| over the batch) at
  each step and takes the top budget rows; this is the share of selected
  rows on which the program's mask differs from that choice, over every
  step but the cohort's first (whose importance comes from the cohort
  before) and every site and layer it spans (the reference's ``sites``).
  A reference whose selection is not a plain top-k (experts chosen before
  their rows) says what it expects with ``expected_mask(dims, site,
  importance, budget)``.

The control in the program's place comes in two forms: the program's own
int8 weight path (``Program(wbits=8)``, harness ``control=True`` on a
selecting method), and the reference itself with float8 weights
(``control_fp8``), whose keys and values stand for the cache and whose
top token at each position is read against the float32 reference.

``ref`` is the cell's reference module and ``dm`` its ``dims``.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.traffic.common import seed_words

SAMPLE_TOKENS = 400  # dense cells: served tokens compared per run


def topk_mask(v: np.ndarray, budget: int) -> np.ndarray:
    """The ``budget`` largest entries (stable: ties go to the lower index)."""
    order = np.argsort(-v.astype(np.float32), kind="stable")
    m = np.zeros(v.shape[0], bool)
    m[order[:budget]] = True
    return m


def budget(n: int, sparsity: float) -> int:
    return int(round((1.0 - sparsity) * n))


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """ref best minus ref logit of ``tokens``, per position."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def served_sequence(sv) -> Tuple[np.ndarray, np.ndarray]:
    """(prompt + served tokens as fed, the served tokens): the prefill's
    token first, then each decoded token; the last one is never fed."""
    tok0 = int(np.argmax(np.asarray(sv.prefill_logits, np.float32)[0]))
    served = np.asarray([tok0] + sv.tokens, np.int32)
    return np.concatenate([sv.item.tokens, served[:-1]]), served


def cohorts(done, slots: int) -> Dict[tuple, list]:
    """Groups of requests that ran every decode step together and filled
    every slot (the batch the selection ranked), keyed by their decode
    calls; each sorted by request id."""
    groups: Dict[tuple, list] = {}
    for sv in done:
        groups.setdefault(tuple(sv.decode_calls), []).append(sv)
    return {key: sorted(g, key=lambda s: s.item.rid) for key, g in groups.items()
            if len(g) == slots and None not in key}


def compare_rows(ref, dm, seed: int, tokens: np.ndarray, served: np.ndarray,
                 p: int, s_len: int, cache: Optional[Dict[str, np.ndarray]],
                 control_fp8: bool, masks_at: Optional[Callable] = None,
                 imp_pos: Optional[List[int]] = None,
                 n_served: Optional[int] = None) -> Tuple[Dict[str, float], dict]:
    """Rows of one prompt length ``p`` and true length ``s_len`` (``tokens``
    may be padded past it). ``served`` (rows, n) are compared at positions
    p-1 .. p+n-2; ``cache``: each entry the reference names, (layers, rows,
    >= s_len, ...), or None to compare no cache; only the first
    ``n_served`` columns count (the rest pad a fixed shape). With
    ``control_fp8`` the float8 reference takes the program's place: its top
    tokens and its cache."""
    import jax.numpy as jnp

    n = served.shape[1]
    pos = [min(p - 1 + j, tokens.shape[1] - 1) for j in range(n)]
    if control_fp8:
        low: Dict[str, list] = {name: [] for name in ref.cache_entries(dm)}

        def keep(layer, entries):
            for name, rows in low.items():
                rows.append(np.asarray(entries[name]))

        lo, _ = ref.forward(dm, seed, tokens, pos, masks_at=masks_at, fp8=True,
                            on_cache=keep)
        served = np.asarray(lo).argmax(-1).astype(np.int32)
        if cache is not None:
            cache = {name: np.stack(rows) for name, rows in low.items()}
    errs: List[float] = []
    errs0: List[float] = []

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def on_cache(layer, entries):
        for name in cache:
            ours = entries[name]
            got = jnp.asarray(cache[name][layer, :, :s_len], jnp.float32)
            errs.append(rel(got, ours[:, :s_len]))
            if layer == 0:
                errs0.append(rel(got[:, p:s_len], ours[:, p:s_len]))

    logits, imps = ref.forward(dm, seed, tokens, pos, masks_at=masks_at, imp_pos=imp_pos,
                               on_cache=on_cache if cache is not None else None)
    out = {"logit_gap": float(gaps(np.asarray(logits), served)[:, :n_served].max())}
    if cache is not None:
        out["kv_rel_err"], out["kv0_rel_err"] = max(errs), max(errs0)
    return out, imps


def check_selecting(ref, dm, seed: int, cohort: list, masks, sparsity: float,
                    cache: Dict[str, np.ndarray], control_fp8: bool = False
                    ) -> Dict[str, float]:
    """``cohort``: the requests of one full batch, in row order; ``cache``:
    the program's cache rows of those requests as the cohort's last step
    left them, (layers, rows, positions, ...) each; ``masks[call][site]``:
    (the site's layers, N)."""
    seqs = [served_sequence(sv) for sv in cohort]
    p = len(cohort[0].item.tokens)
    n = len(cohort[0].tokens)
    tokens = np.stack([s for s, _ in seqs])
    served = np.stack([t for _, t in seqs])
    calls = cohort[0].decode_calls
    s_len = tokens.shape[1]
    spans = {site: layers for site, (_, layers) in ref.sites(dm).items()}

    def masks_at(layer):
        out = {}
        for site in masks[calls[0]]:
            if layer not in spans[site]:
                continue
            row = spans[site].index(layer)
            m = np.ones((s_len, masks[calls[0]][site].shape[1]), np.float32)
            for j, c in enumerate(calls[: s_len - p]):
                m[p + j] = masks[c][site][row]
            out[site] = m
        return out

    out, imps = compare_rows(ref, dm, seed, tokens, served, p, s_len, cache, control_fp8,
                             masks_at=masks_at, imp_pos=list(range(p, p + n - 1)))
    expected = getattr(ref, "expected_mask", lambda dm, site, imp, b: topk_mask(imp, b))
    differ = total = 0
    for j in range(1, n):
        for site, imp in imps.items():
            b = budget(imp.shape[-1], sparsity)
            for row in range(imp.shape[0]):
                want = expected(dm, site, imp[row, j - 1], b)
                differ += int(np.sum(want != masks[calls[j]][site][row]))
                total += 2 * b
    out["mask_mismatch"] = differ / total
    return out


def check_dense(ref, dm, seed: int, done, max_seq: int, n_pos: int,
                kept: Optional[tuple], control_fp8: bool) -> Dict[str, float]:
    """A sample drawn from the seed, the longest request first, of about
    ``SAMPLE_TOKENS`` served tokens; ``kept``: (request, its cache rows)
    whose cache entries are compared too. ``n_pos``: served tokens read
    per request at most (the longest output plus one), fixed per cell so
    the reference compiles once."""
    rng = np.random.default_rng(seed_words(seed) + [0xD0])
    order = sorted(done, key=lambda s: (-len(s.tokens), s.item.rid))
    sample, count = [order[0]], len(order[0].tokens) + 1
    rest = [sv for sv in order[1:] if kept is None or sv is not kept[0]]
    rng.shuffle(rest)
    if kept is not None and kept[0] is not order[0]:
        sample.append(kept[0])
        count += len(kept[0].tokens) + 1
    for sv in rest:
        if count >= SAMPLE_TOKENS:
            break
        sample.append(sv)
        count += len(sv.tokens) + 1
    out: Dict[str, float] = {}
    for sv in sample:
        seq, served = served_sequence(sv)
        p, n = len(sv.item.tokens), len(served)
        tokens = np.zeros((1, max_seq), np.int32)
        tokens[0, : len(seq)] = seq
        padded = np.zeros((1, n_pos), np.int32)
        padded[0, :n] = served
        kv = kept[1] if kept is not None and sv is kept[0] else None
        got, _ = compare_rows(ref, dm, seed, tokens, padded, p, len(seq), kv, control_fp8,
                              n_served=n)
        for k, v in got.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def compare(readings: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    missing = set(readings) ^ set(limits)
    if missing:
        raise KeyError(f"readings and limits disagree on {sorted(missing)}")
    return {k: {"value": readings[k], "limit": float(limits[k])} for k in sorted(readings)}
