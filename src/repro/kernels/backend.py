"""Decode execution backends: the selection → plan → kernel chain's last hop.

Up to PR 4 the DMA gather kernels (chunk_gather_dma.py) were dispatched
standalone and parity-tested while the decode hot path computed through the
dense-weights-masked reference — the kernels never executed a served token.
``ExecutionBackend`` closes that gap: the planned decode path
(models/transformer.py ``block_decode`` with a chunk-plan carry) routes its
sparse projections through one of two implementations selected by
``ServeEngine(backend=...)`` / ``launch.serve --backend``:

  * ``reference`` (default) — pure-jnp masked matmuls, restructured as the
    kernel's **schedule twin**: f32 accumulation over ``block_rows``-sized
    row blocks in ascending order, the exact arithmetic the DMA kernel's
    slot-rotation loop performs (interpret mode executes the same jnp ops
    per block). Blocks outside the chunk tables contribute exact zeros
    (the input is pre-masked), so skipping them — as the kernel does — or
    adding them changes nothing. Result: the two backends are **bitwise
    identical**, and byte-identical decode tokens become the system's
    strongest correctness invariant (tests/test_backend.py pins it).
  * ``kernel`` — the PR-4 Pallas kernels consume the decode plan's
    ``kstarts``/``ksizes``/``mlp_kernel_plan`` lanes directly:
    ``chunk_gather_mlp_dma`` replaces the masked dense SwiGLU (ONE dispatch
    for gate/up/down, SwiGLU intermediate resident in VMEM) and
    ``chunk_gather_matmul_dma`` serves the single-site projections (q/k/v
    off the ``hidden_attn`` site, attn_out's ``wo``, both matrices of the
    non-gated gelu MLP — the full decode hot path). Compiled on TPU,
    interpret mode on the CPU test platform (``resolve_interpret``).

Both implementations compute the SAME masked-matmul semantics of paper
App. B.2 — the backend only changes how the arithmetic is realized, never
which neurons participate, so every future perf PR lands behind this
switch with byte-identity as its acceptance gate.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .chunk_gather_dma import chunk_gather_matmul_dma, chunk_gather_mlp_dma

BACKENDS = ("reference", "kernel")


def validate_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown execution backend {name!r}; expected one of {BACKENDS}"
        )
    return name


def pick_tile(dim: int, cap: int = 128) -> int:
    """Largest power-of-two tile ≤ ``cap`` dividing ``dim`` — the kernels
    require output dims to split evenly into tiles (reduced-config d_ff
    values like 704 need 64-wide tiles; full-size dims take the 128 MXU
    lane width)."""
    t = cap
    while t >= 8:
        if dim % t == 0:
            return t
        t //= 2
    raise ValueError(
        f"dim {dim} has no power-of-two tile divisor >= 8 — the kernel "
        "backend needs dims divisible by 8"
    )


def blocked_masked_matmul(
    xm: jnp.ndarray,  # (B, N) pre-masked input, any float dtype
    w: jnp.ndarray,  # (N, D); int8 payload when scales is given
    block_rows: int = 8,
    scales: jnp.ndarray | None = None,  # (N // block_rows,) f32 per-block
) -> jnp.ndarray:
    """The DMA gather kernel's schedule twin: y = Σ_blocks xm_blk @ w_blk in
    ascending ``block_rows`` blocks, f32 accumulation — per output element
    the identical multiply/add sequence the kernel's fori_loop performs, so
    the result is bitwise equal to interpret-mode ``chunk_gather_matmul_dma``
    on any chunk table covering the mask (uncovered blocks see zeroed xm
    rows and contribute exact +0.0).

    The per-block partial products are independent, so they run as ONE
    batched einsum (each (B, block_rows) · (block_rows, D) contraction is
    elementwise identical to the kernel's per-step dot); only the f32
    additions — the order-sensitive part — stay sequential. That keeps the
    decode hot path one fused matmul + nb cheap adds instead of nb
    serialized dots (bitwise equality across both forms and the kernel is
    pinned by tests/test_backend.py).

    With ``scales`` (the quantized chunk format): ``w`` is the int8 payload
    and each block's f32 partial product ``xm_blk @ q_blk.astype(f32)`` is
    multiplied by the block's scale inside the accumulation step,
    ``acc + part * scale`` — the kernel's exact expression, so whatever the
    compiler makes of it (XLA:CPU fuses it into one FMA) it makes of both,
    keeping the twins bitwise equal at 8 bits.

    The contraction runs at ``Precision.HIGHEST``, as the kernel's does: on
    TPU the default f32 matmul precision is a single bf16 pass."""
    b, n = xm.shape
    if n % block_rows:
        raise ValueError(f"N={n} must be a multiple of block_rows={block_rows}")
    nb = n // block_rows
    xb = xm.astype(jnp.float32).reshape(b, nb, block_rows)
    wb = w.astype(jnp.float32).reshape(nb, block_rows, w.shape[1])
    parts = jnp.einsum("bkr,krd->kbd", xb, wb,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    sc = None if scales is None else scales.astype(jnp.float32)

    def body(k, acc):
        return acc + (parts[k] if sc is None else parts[k] * sc[k])

    return jax.lax.fori_loop(
        0, nb, body, jnp.zeros((b, w.shape[1]), jnp.float32)
    )


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas mode for the gather kernels: an explicit bool is kept; None
    compiles them on TPU and interprets them on the CPU test platform. Any
    other platform raises — the kernels are written for the TPU, and no
    path quietly swaps the device for the interpreter."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas gather kernels compile for TPU and are interpreted only "
        f"on the CPU test platform; JAX's default backend is {platform!r}"
    )


@dataclasses.dataclass(frozen=True)
class ExecutionBackend:
    """Dispatch object carried by ``SparseExecution`` into the model blocks.

    ``interpret``: None = compiled on TPU, interpreted on the CPU test
    platform, an error elsewhere (``resolve_interpret``) — resolved at
    construction so the jit caches stay stable.
    ``prefetch_depth``: the DMA kernels' VMEM slot count − 1; numerics are
    depth-invariant (the schedule only re-times the same fetches), so
    tokens stay byte-identical at every depth.

    ``mesh`` (sharded serving, sharding/serve.py): when set, every weight
    / scales operand is constrained to FULL REPLICATION at the compute
    boundary — the explicit all-gather of each model shard's slice that
    realizes the SwiGLU down-projection all-reduce as gather-then-ordered-
    sum. Storage and I/O stay sharded (the leaves live model-partitioned
    in device memory and the plan's per-shard byte lanes price each
    shard's slice); only the fold's operands are gathered, so the f32
    accumulation runs in the exact single-device block order and decode
    tokens are byte-identical to the 1×1 mesh BY CONSTRUCTION. Without
    the constraint GSPMD is free to partition the contraction over the
    sharded rows and reassociate the partial sums — measurably not
    bitwise-stable. Also pins the kernel path's operand layout (pallas
    calls need replicated operands on host meshes anyway).

    Both paths pass their activations and results through
    ``jax.lax.optimization_barrier``, so the call is a fusion boundary on
    either backend. A Pallas call always is one; the twin's jnp ops are
    not, and XLA keeps bf16 intermediates in f32 wherever a fusion lets it
    (``xla_allow_excess_precision``, on by default). Without the fence the
    code AROUND the backend (norms, residual adds, the q/k/v casts) was
    fused, and so rounded, differently in the two decode programs: on a
    v5e the first decode step's logits differed by up to 7e-3 relative and
    the greedy tokens parted after two steps, though every backend op
    agreed bitwise on its own.
    """

    name: str = "reference"
    prefetch_depth: int = 1
    interpret: bool = True
    block_rows: int = 8
    tile_cap: int = 128
    mesh: Optional[Mesh] = None

    @staticmethod
    def create(
        name: str = "reference",
        prefetch_depth: int = 1,
        interpret: Optional[bool] = None,
        block_rows: int = 8,
        tile_cap: int = 128,
        mesh: Optional[Mesh] = None,
    ) -> "ExecutionBackend":
        validate_backend(name)
        if prefetch_depth < 0:
            raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
        return ExecutionBackend(
            name=name,
            prefetch_depth=prefetch_depth,
            interpret=resolve_interpret(interpret),
            block_rows=block_rows,
            tile_cap=tile_cap,
            mesh=mesh,
        )

    @property
    def is_kernel(self) -> bool:
        return self.name == "kernel"

    def _gather(self, w: Optional[jnp.ndarray]) -> Optional[jnp.ndarray]:
        """All-gather a (possibly model-sharded) weight/scales operand to
        full replication at the compute boundary — see the class docstring.
        No-op without a serve mesh."""
        if self.mesh is None or w is None:
            return w
        return jax.lax.with_sharding_constraint(
            w, NamedSharding(self.mesh, PartitionSpec())
        )

    def _kernel_call(self, kernel, *operands):
        """Dispatch a Pallas kernel. Under a serve mesh it runs as one
        whole-operand program per device (``shard_map`` with every operand
        and output replicated): Mosaic kernels are not partitioned
        automatically, and the replicated fold is the gather-then-ordered-
        sum the class docstring describes."""
        if self.mesh is None:
            return kernel(*operands)
        return jax.shard_map(kernel, mesh=self.mesh, in_specs=PartitionSpec(),
                             out_specs=PartitionSpec(),
                             check_vma=False)(*operands)

    # -- single-site projection (attn_out wo; gelu MLP fc/proj) -------------
    @jax.named_scope("decode.gather")
    def project(
        self,
        w: jnp.ndarray,  # (N, D); int8 payload when scales is given
        x: jnp.ndarray,  # (B, N)
        mask: jnp.ndarray,  # (N,) exact selected-row mask (float or bool)
        starts: jnp.ndarray,  # (K,) block-aligned chunk table (kernel lane)
        sizes: jnp.ndarray,  # (K,)
        scales: jnp.ndarray | None = None,  # (N // block_rows,) f32
        checksums: jnp.ndarray | None = None,  # (N // block_rows,) u32
    ) -> jnp.ndarray:
        """y (B, D) f32 = (x · mask) @ w. The input is pre-masked by the
        EXACT mask for both backends, so the kernel's outward block rounding
        gathers only zeroed extra rows — masked-matmul semantics hold and
        the two implementations agree bitwise. With ``scales`` (8-bit chunk
        storage) both backends scale each block's f32 partial product as
        it accumulates, preserving the bitwise twin property. With
        ``checksums`` the kernel path carries each block's integrity word
        into SMEM with the call (verification happens at the selection
        boundary); the reference path — whose operands never leave device
        memory — ignores it. Output is bit-identical either way."""
        x, mask = jax.lax.optimization_barrier((x, mask))
        xm = (x * mask.astype(x.dtype)).astype(jnp.float32)
        w, scales = self._gather(w), self._gather(scales)
        if self.is_kernel:
            kernel = functools.partial(
                chunk_gather_matmul_dma,
                block_rows=self.block_rows,
                tile_d=pick_tile(w.shape[1], self.tile_cap),
                prefetch_depth=self.prefetch_depth,
                interpret=self.interpret,
            )
            y = self._kernel_call(kernel, w, xm, starts, sizes, scales,
                                  self._gather(checksums))
        else:
            y = blocked_masked_matmul(xm, w, self.block_rows, scales)
        return jax.lax.optimization_barrier(y)

    # -- fused multi-site SwiGLU MLP -----------------------------------------
    @jax.named_scope("decode.gather")
    def swiglu_mlp(
        self,
        w_gate: jnp.ndarray,  # (N, F)
        w_up: jnp.ndarray,  # (N, F)
        w_down: jnp.ndarray,  # (F, D)
        x: jnp.ndarray,  # (B, N)
        hidden_mask: jnp.ndarray,  # (N,) exact hidden_mlp-site mask
        ffn_mask: jnp.ndarray,  # (F,) exact ffn-site mask
        starts: jnp.ndarray,  # (2, K) plan lanes: hidden_mlp, ffn
        sizes: jnp.ndarray,  # (2, K)
        scales: Optional[Tuple] = None,  # (sg, su, sd) per-block f32 lanes
        checksums: Optional[Tuple] = None,  # (cg, cu, cd) per-block u32 lanes
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (y (B, D) f32, h (B, F) f32) where h is the UNMASKED
        SwiGLU intermediate swish(xm @ w_gate) * (xm @ w_up) — the decode
        path records |h| as the next refresh's ffn-lane importance, so it
        must be the pre-mask value on both backends. ``scales`` switches
        all three weights to the quantized chunk format (int8 payloads +
        per-block scale lanes), dequantized identically on both backends.
        ``checksums`` adds the kernel path's per-block integrity-word lanes
        (carried, not verified; see ``project``) — bit-identical either
        way."""
        x, hidden_mask, ffn_mask = jax.lax.optimization_barrier(
            (x, hidden_mask, ffn_mask))
        xm = (x * hidden_mask.astype(x.dtype)).astype(jnp.float32)
        fm = ffn_mask.astype(jnp.float32)
        w_gate, w_up, w_down = (
            self._gather(w_gate), self._gather(w_up), self._gather(w_down)
        )
        if scales is not None:
            scales = tuple(self._gather(s) for s in scales)
        if checksums is not None:
            checksums = tuple(self._gather(c) for c in checksums)
        if self.is_kernel:
            kernel = functools.partial(
                chunk_gather_mlp_dma,
                block_rows=self.block_rows,
                tile_f=pick_tile(w_gate.shape[1], self.tile_cap),
                tile_d=pick_tile(w_down.shape[1], self.tile_cap),
                prefetch_depth=self.prefetch_depth,
                interpret=self.interpret,
                return_h=True,
            )
            return jax.lax.optimization_barrier(self._kernel_call(
                kernel, w_gate, w_up, w_down, xm, starts, sizes, fm, scales,
                checksums))
        sg, su, sd = scales if scales is not None else (None, None, None)
        g = blocked_masked_matmul(xm, w_gate, self.block_rows, sg)
        u = blocked_masked_matmul(xm, w_up, self.block_rows, su)
        # the kernel's literal sigmoid expression (jax.nn.sigmoid lowers to
        # a different, numerically-stable formulation — bitwise matters here)
        h = g * (1.0 / (1.0 + jnp.exp(-g))) * u
        y = blocked_masked_matmul(h * fm[None, :], w_down, self.block_rows, sd)
        return jax.lax.optimization_barrier((y, h))
