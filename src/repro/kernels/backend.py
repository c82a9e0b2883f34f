"""Decode execution backends: the selection → plan → kernel chain's last hop.

Up to PR 4 the DMA gather kernels (chunk_gather_dma.py) were dispatched
standalone and parity-tested while the decode hot path computed through the
dense-weights-masked reference — the kernels never executed a served token.
``ExecutionBackend`` closes that gap: the planned decode path
(models/transformer.py ``block_decode`` with a chunk-plan carry) routes its
sparse projections through one of two implementations selected by
``ServeEngine(backend=...)`` / ``launch.serve --backend``:

  * ``reference`` (default) — pure-jnp masked matmuls, restructured as the
    kernel's **schedule twin**: f32 accumulation over super-blocks (128
    input rows at every real width, ``superblock_rows``) in ascending
    order, each super-block's partial one ``Precision.HIGHEST`` contraction
    over its rows — the exact arithmetic one step of the DMA kernel's
    slot-rotation loop performs (interpret mode executes the same jnp ops
    per step). Super-blocks and blocks outside the chunk tables contribute
    exact zeros (the input is pre-masked), so skipping them — as the kernel
    does — or adding them changes nothing. Result: the two backends are
    **bitwise identical**, and byte-identical decode tokens become the
    system's strongest correctness invariant (tests/test_backend.py pins it).
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

from .chunk_gather_dma import (
    chunk_gather_expert_down_dma,
    chunk_gather_expert_up_dma,
    chunk_gather_matmul_dma,
    chunk_gather_mlp_dma,
    chunk_lanes,
    superblock_rows,
)

BACKENDS = ("reference", "kernel")


def validate_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown execution backend {name!r}; expected one of {BACKENDS}"
        )
    return name


def blocked_masked_matmul(
    xm: jnp.ndarray,  # (B, N) pre-masked input, any float dtype
    w: jnp.ndarray,  # (N, D); int8 payload when scales is given
    block_rows: int = 8,
    scales: jnp.ndarray | None = None,  # (N // block_rows,) f32 per-block
    operand_dtype=None,
) -> jnp.ndarray:
    """The DMA gather kernel's schedule twin: y = Σ_sb xm_sb @ w_sb over
    ascending super-blocks of ``superblock_rows(N)`` rows, f32
    accumulation — per output element the identical multiply/add sequence
    the kernel's step loop performs, so the result is bitwise equal to
    interpret-mode ``chunk_gather_matmul_dma`` on any chunk table covering
    the mask (uncovered rows see zeroed xm and contribute exact +0.0).

    The partial products are independent, so they run as ONE batched
    contraction over (super-block, column piece) pairs, each
    (B, rows) · (rows, ``chunk_lanes(D)``) the shape of one of the
    kernel's per-step dots (XLA:CPU rounds a dot differently at other
    widths); only the f32 additions — the order-sensitive part — stay
    sequential.

    With ``scales`` (the quantized chunk format): ``w`` is the int8 payload
    and each input lane is multiplied by its block's scale before the
    contraction, ``xm · scale_lanes`` — the kernel's exact expression, so
    the twins stay bitwise equal at 8 bits.

    The contraction runs at ``Precision.HIGHEST``, as the kernel's does: on
    TPU the default f32 matmul precision is a single bf16 pass. With
    ``operand_dtype`` (the expert down phase) both operands are cast to it
    and contracted in one pass into f32 instead, as that kernel does."""
    b, n = xm.shape
    d = w.shape[1]
    if n % block_rows:
        raise ValueError(f"N={n} must be a multiple of block_rows={block_rows}")
    sb, c = superblock_rows(n, block_rows), chunk_lanes(d)
    nsb, nc = n // sb, d // c
    xm = xm.astype(jnp.float32)
    if scales is not None:
        xm = xm * jnp.repeat(scales.astype(jnp.float32), block_rows)[None, :]
    dtype = jnp.float32 if operand_dtype is None else operand_dtype
    # (super-block, piece) pairs as one batch dimension
    xb = jnp.repeat(xm.astype(dtype).reshape(b, nsb, sb).transpose(1, 0, 2), nc, axis=0)
    wb = (w.astype(dtype).reshape(nsb, sb, nc, c)
          .transpose(0, 2, 1, 3).reshape(nsb * nc, sb, c))
    parts = jax.lax.dot_general(
        xb, wb, (((2,), (1,)), ((0,), (0,))),
        precision=(jax.lax.Precision.HIGHEST if operand_dtype is None
                   else jax.lax.Precision.DEFAULT),
        preferred_element_type=jnp.float32,
    ).reshape(nsb, nc, b, c).transpose(0, 2, 1, 3).reshape(nsb, b, d)
    return jax.lax.fori_loop(
        0, nsb, lambda k, acc: acc + parts[k], jnp.zeros((b, d), jnp.float32)
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
    mesh: Optional[Mesh] = None

    @staticmethod
    def create(
        name: str = "reference",
        prefetch_depth: int = 1,
        interpret: Optional[bool] = None,
        block_rows: int = 8,
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
        storage) both backends multiply each input lane by its block's
        scale before the contraction, preserving the bitwise twin property. With
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

    # -- routed experts, read in place in the stacked weights ----------------
    @jax.named_scope("decode.gather")
    def expert_up(
        self,
        w_gate: jnp.ndarray,  # (L, E, d, F) the whole stack
        w_up: jnp.ndarray,  # (L, E, d, F)
        layer: jnp.ndarray,  # () int32
        ids: jnp.ndarray,  # (E,) routed held experts first
        count: jnp.ndarray,  # () how many are routed
        x: jnp.ndarray,  # (B, d)
    ) -> jnp.ndarray:
        """act (B, E·F) f32: silu(x · gate) * (x · up) in the columns of
        the routed experts, zero in the others. The kernel reads each routed
        expert's matrices in place (``chunk_gather_expert_up_dma``); the
        twin computes every held expert of the layer with the kernel's
        per-expert contraction and zeroes the unrouted."""
        x = jax.lax.optimization_barrier(x)
        if self.is_kernel:
            act = chunk_gather_expert_up_dma(
                w_gate, w_up, layer, ids, count, x,
                prefetch_depth=self.prefetch_depth, interpret=self.interpret)
        else:
            wg, wu = w_gate[layer], w_up[layer]
            e, d, f = wg.shape
            xb = jnp.broadcast_to(x.astype(wg.dtype)[None], (e,) + x.shape)
            dims = (((2,), (1,)), ((0,), (0,)))
            g = jax.lax.dot_general(xb, wg, dims, preferred_element_type=jnp.float32)
            u = jax.lax.dot_general(xb, wu, dims, preferred_element_type=jnp.float32)
            on = jnp.zeros((e,), bool).at[ids].set(jnp.arange(e) < count)
            act = jnp.where(on[:, None, None], g * (1.0 / (1.0 + jnp.exp(-g))) * u, 0.0)
            act = act.transpose(1, 0, 2).reshape(x.shape[0], e * f)
        return jax.lax.optimization_barrier(act)

    @jax.named_scope("decode.gather")
    def expert_down(
        self,
        w_down: jnp.ndarray,  # (L, E, F, d) the whole stack
        layer: jnp.ndarray,  # () int32
        x: jnp.ndarray,  # (B, E·F)
        mask: jnp.ndarray,  # (E·F,) exact selected rows
        starts: jnp.ndarray,  # (K,) block table over the E·F rows
        sizes: jnp.ndarray,
    ) -> jnp.ndarray:
        """y (B, d) f32 = (x · mask) @ down[layer] rows, bf16 operands into
        f32: the kernel gathers the table's rows in place
        (``chunk_gather_expert_down_dma``), the twin contracts the same
        super-blocks in the same order."""
        x, mask = jax.lax.optimization_barrier((x, mask))
        xm = (x * mask.astype(x.dtype)).astype(jnp.float32)
        if self.is_kernel:
            y = chunk_gather_expert_down_dma(
                w_down, layer, xm, starts, sizes, block_rows=self.block_rows,
                prefetch_depth=self.prefetch_depth, interpret=self.interpret)
        else:
            _, e, f, d = w_down.shape
            y = blocked_masked_matmul(xm, w_down[layer].reshape(e * f, d),
                                      self.block_rows, operand_dtype=w_down.dtype)
        return jax.lax.optimization_barrier(y)
