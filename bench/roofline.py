"""Operations and bytes the algorithm needs, and the chip's peaks.

A gather kernel's work is counted from the step's plan: the rows its masks
select, times the width of each matrix streamed, at the stored width, plus
the activations it reads and writes. It does not count the 8-row blocks a
kernel happens to fetch, so the same work reads the same whatever
implements it. The least time of a call is the larger of its operations
over peak and its bytes over peak bandwidth; a kernel's share of its
roofline is that least time over its measured time.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

from bench.weights import Dims

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
ACT_BYTES = 4  # the kernels take and return float32 activations


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def site_inputs(dm: Dims) -> Dict[str, int]:
    return {"hidden_attn": dm.d, "attn_out": dm.heads * dm.head_dim,
            "hidden_mlp": dm.d, "ffn": dm.f}


def kernel_calls(dm: Dims) -> Dict[str, List[List[Tuple[str, int]]]]:
    """Per layer, each kernel's calls; a call is a list of (site, output
    width) for the matrices it streams."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    proj = [[("hidden_attn", q)], [("hidden_attn", kv)], [("hidden_attn", kv)],
            [("attn_out", dm.d)]]
    if dm.mlp == "swiglu":
        return {"matmul_dma": proj,
                "mlp_dma": [[("hidden_mlp", dm.f), ("hidden_mlp", dm.f), ("ffn", dm.d)]]}
    return {"matmul_dma": proj + [[("hidden_mlp", dm.f)], [("ffn", dm.d)]]}


def call_work(call: List[Tuple[str, int]], rows: Dict[str, float], dm: Dims,
              batch: int, weight_bytes: float) -> Tuple[float, float]:
    """(operations, bytes) of one call with ``rows[site]`` rows selected.

    Activations: the call reads the input of its first site (a fused MLP
    makes the down projection's input itself) and writes each output,
    plus, for a fused MLP, the intermediate it returns."""
    inputs = site_inputs(dm)
    flops = sum(2.0 * batch * rows[site] * cols for site, cols in call)
    weights = sum(rows[site] * cols * weight_bytes for site, cols in call)
    acts = batch * inputs[call[0][0]] * ACT_BYTES
    sites = {site for site, _ in call}
    if len(sites) > 1:  # fused: y plus the returned intermediate
        acts += batch * (dm.d + dm.f) * ACT_BYTES
    else:
        acts += batch * sum(cols for _, cols in call) * ACT_BYTES
    return flops, weights + acts


def least_time_per_call(kernel: str, dm: Dims, rows: Dict[str, float], batch: int,
                        peaks: Dict[str, float], wbits: int = 16) -> float:
    """Mean least time of one call of ``kernel`` over a layer's calls."""
    calls = kernel_calls(dm)[kernel]
    peak = peaks["int8_ops"] if wbits == 8 else peaks["bf16_flops"]
    wb = 1.0 if wbits == 8 else 2.0
    total = 0.0
    for call in calls:
        flops, nbytes = call_work(call, rows, dm, batch, wb)
        total += max(flops / peak, nbytes / peaks["hbm_bytes_per_s"])
    return total / len(calls)


def dense_flops_per_token(dm: Dims, context: float) -> float:
    """Operations of one dense decode token: every matmul of every layer
    and the head, plus attention's QK and PV over ``context`` positions."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    mlp = (3 if dm.mlp == "swiglu" else 2) * dm.d * dm.f
    per_layer = dm.d * q + 2 * dm.d * kv + q * dm.d + mlp
    return 2.0 * (dm.layers * per_layer + dm.d * dm.vocab) + 4.0 * dm.layers * q * context
