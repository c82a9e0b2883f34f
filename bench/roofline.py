"""Operations and bytes the algorithm needs, and the chip's peaks.

A gather kernel's work is counted from the step's plan: the rows its masks
select, times the width of each matrix streamed, at the stored width, plus
the activations it reads and writes. It does not count the 8-row blocks a
kernel happens to fetch, so the same work reads the same whatever
implements it. The least time of a call is the larger of its operations
over peak and its bytes over peak bandwidth; a kernel's share of its
roofline is that least time over its measured time. Which calls a kernel
makes, and the width of each site, come from the cell's reference module.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
ACT_BYTES = 4  # the kernels take and return float32 activations


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def call_work(call: List[Tuple[str, int]], rows: Dict[str, float], inputs: Dict[str, int],
              batch: int, weight_bytes: float) -> Tuple[float, float]:
    """(operations, bytes) of one call with ``rows[site]`` rows selected;
    ``inputs[site]``: the site's input width.

    Activations: the call reads the input of its first site (a fused MLP
    makes its last matrix's input itself) and writes each output, plus,
    for a fused MLP, the intermediate it returns (that last input)."""
    flops = sum(2.0 * batch * rows[site] * cols for site, cols in call)
    weights = sum(rows[site] * cols * weight_bytes for site, cols in call)
    acts = batch * inputs[call[0][0]] * ACT_BYTES
    sites = {site for site, _ in call}
    if len(sites) > 1:  # fused: y plus the returned intermediate
        acts += batch * (call[-1][1] + inputs[call[-1][0]]) * ACT_BYTES
    else:
        acts += batch * sum(cols for _, cols in call) * ACT_BYTES
    return flops, weights + acts


def least_time_per_call(ref, dm, kernel: str, rows: Dict[str, float], batch: int,
                        peaks: Dict[str, float], wbits: int = 16) -> float:
    """Mean least time of one call of ``kernel`` over the calls that the
    cell's reference module ``ref`` lists (``kernel_calls``)."""
    calls = ref.kernel_calls(dm)[kernel]
    inputs = {site: width for site, (width, _) in ref.sites(dm).items()}
    peak = peaks["int8_ops"] if wbits == 8 else peaks["bf16_flops"]
    wb = 1.0 if wbits == 8 else 2.0
    total = 0.0
    for call in calls:
        flops, nbytes = call_work(call, rows, inputs, batch, wb)
        total += max(flops / peak, nbytes / peaks["hbm_bytes_per_s"])
    return total / len(calls)
