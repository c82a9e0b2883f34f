"""Pallas TPU kernels for the paper's compute hot-spot: chunk-gathered
sparse matmuls driven by the utility-guided selection's chunk tables."""
from .backend import (
    BACKENDS,
    ExecutionBackend,
    blocked_masked_matmul,
    validate_backend,
)
from .chunk_gather_dma import (
    chunk_gather_expert_down_dma,
    chunk_gather_expert_up_dma,
    chunk_gather_matmul_dma,
    chunk_gather_mlp_dma,
    masks_to_block_tables,
    pick_tile,
)
from .chunk_gather_matmul import align_chunk_table, chunk_gather_matmul
from .chunk_gather_swiglu import chunk_gather_swiglu
from .ops import (
    plan_to_kernel_table,
    sparse_matmul,
    sparse_matmul_dma,
    sparse_mlp_fused,
    sparse_swiglu,
)
from .quantize import (
    SCALE_BYTES,
    dequantize_rows,
    quantize_params,
    quantize_rows,
)
from .ref import (
    chunk_gather_matmul_ref,
    chunk_gather_mlp_ref,
    chunk_gather_swiglu_ref,
    chunk_table_to_mask,
)
