"""Tensor/sequence-parallel core ops — analogue of
``torchdistpackage/parallel/tensor_parallel/tp_utils.py`` (248 LoC).

The reference implements Megatron-style autograd regions by hand
(`_ReduceFromModelParallelRegion`, `_GatherFromSequenceParallelRegion`,
`_ReduceScatterToSequenceParallelRegion`, tp_utils.py:39-149) because eager
PyTorch needs explicit backward rules.  Under ``shard_map`` + JAX AD the
transposes come for free and *correctly*:

- ``all_gather``   (SP gather, fwd)  <-AD->  ``psum_scatter`` (bwd)
- ``psum_scatter`` (SP scatter, fwd) <-AD->  ``all_gather``   (bwd)
- replicated operand entering a per-shard matmul (``pvary``) <-AD-> ``psum``
  of its gradient — this is the Megatron "f" region whose backward all-reduce
  the reference *misses* in non-SP mode (SURVEY.md §3.4); here it cannot be
  missed.

Unlike the reference, which keeps a module-global ``TP_GROUP`` disconnected
from its own topology singleton (tp_utils.py:7-15 — an integration gap), the
default axis here is the topology's canonical ``'tensor'`` axis, overridable
per call.
"""

from __future__ import annotations

from typing import Optional

import jax

from jax.lax import axis_size
import jax.numpy as jnp

from ...dist.topology import TENSOR_AXIS

# Default mesh-axis name used by TP layers; override per-call via ``axis=``.
_TP_AXIS = TENSOR_AXIS


def set_tp_axis(name: str) -> None:
    """Analogue of ``set_tp_group`` (tp_utils.py:12-15)."""
    global _TP_AXIS
    _TP_AXIS = name


def get_tp_axis() -> str:
    return _TP_AXIS


def tp_size() -> int:
    """Axis size — traced-safe inside shard_map."""
    return axis_size(_TP_AXIS)


# --------------------------------------------------------------------- regions
# All of these are *traced* ops for use inside shard_map over the TP axis.
# seq_dim defaults to 1 for [batch, seq, hidden] layout (TPU-friendly; the
# reference uses seq-first dim 0, tp_utils.py:52-108 — layout is a free choice
# here since XLA owns the memory layout anyway).


def reduce_from_tp(x: jnp.ndarray, axis: Optional[str] = None) -> jnp.ndarray:
    """Forward all-reduce over the TP axis (row-parallel output); backward is
    identity — exactly `_ReduceFromModelParallelRegion` (tp_utils.py:39-49)."""
    return jax.lax.psum(x, axis or _TP_AXIS)


def gather_from_sp(
    x: jnp.ndarray, axis: Optional[str] = None, seq_dim: int = 1,
    compress: Optional[str] = None,
) -> jnp.ndarray:
    """SP -> full: fwd all-gather along the sequence dim, bwd reduce-scatter
    (`_GatherFromSequenceParallelRegion`, tp_utils.py:126-149).

    ``compress='int8'``: the gather rides the quantized ring
    (``dist.compressed.int8_ring_all_gather`` — 1 int8 byte/elem + scale
    sideband on the wire), and its custom VJP makes the backward's
    activation-grad reduce-scatter ride the int8 wire too.  Opt in via
    ``TransformerConfig(ag_compress='int8')`` (layers.py decides per
    boundary against ``compress_min_bytes``)."""
    if compress == "int8":
        from ...dist.compressed import int8_ring_all_gather

        return int8_ring_all_gather(x, axis or _TP_AXIS, seq_dim)
    return jax.lax.all_gather(x, axis or _TP_AXIS, axis=seq_dim, tiled=True)


def scatter_to_sp(
    x: jnp.ndarray, axis: Optional[str] = None, seq_dim: int = 1,
    compress: Optional[str] = None,
) -> jnp.ndarray:
    """Full -> SP: fwd reduce-scatter along the sequence dim, bwd all-gather
    (`_ReduceScatterToSequenceParallelRegion`, tp_utils.py:110-123).

    ``compress='int8'``: the row-parallel partial sums reduce through the
    quantized ring (``dist.compressed.int8_ring_reduce_scatter``), with the
    backward's all-gather quantized via the custom VJP."""
    if compress == "int8":
        from ...dist.compressed import int8_ring_reduce_scatter

        return int8_ring_reduce_scatter(x, axis or _TP_AXIS, seq_dim)
    return jax.lax.psum_scatter(x, axis or _TP_AXIS, scatter_dimension=seq_dim, tiled=True)


def split_to_sp(x: jnp.ndarray, axis: Optional[str] = None, seq_dim: int = 1) -> jnp.ndarray:
    """Full -> SP without reduction: each shard keeps its sequence slice; bwd
    all-gathers (`_split_along_first_dim`, tp_utils.py:88-108).  Used at the
    model boundary to enter SP from a replicated activation."""
    ax = axis or _TP_AXIS
    n = axis_size(ax)
    idx = jax.lax.axis_index(ax)
    if x.shape[seq_dim] % n != 0:
        raise ValueError(f"seq dim {x.shape[seq_dim]} not divisible by TP size {n}")
    chunk = x.shape[seq_dim] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=seq_dim)


# ------------------------------------------------------- collective matmul
# Manual decompositions of the two SP block-boundary patterns into
# ppermute rings whose per-chunk transfers overlap with partial matmuls —
# the Megatron-LM "collective matmul" (Wang et al., "Overlap
# Communication with Dependent Computation via Decomposition"): instead
# of a blocking all-gather followed by one big matmul, each ring step's
# ppermute of a sequence chunk is independent of that step's partial
# matmul, so XLA's latency-hiding scheduler (dist/overlap.py presets)
# runs them concurrently.  The loops are python-unrolled (TP sizes are
# small) precisely so the scheduler sees n independent ppermute/matmul
# pairs instead of a serialized while-loop body.


def ring_ag_matmul(x, mm, axis: Optional[str] = None, out_seq_dim: int = 1):
    """``mm(all_gather(x))`` without materializing the gather first.

    ``x``: the sequence-sharded chunk ``[B, s_local, D]``; ``mm`` maps one
    chunk to its output (any pytree of arrays whose ``out_seq_dim`` is the
    sequence dim) and must be row-wise in the sequence (true for dense
    projections + pointwise activations).  Each of the ``n`` ring steps
    computes ``mm`` on the chunk currently held and forwards the raw chunk
    to the next shard; the chunk outputs are placed at their owner's
    global offset, reproducing ``mm(gather_from_sp(x))`` exactly (up to
    summation order).  AD transposes the ring into a reverse ring — the
    backward's reduce-scatter is decomposed and overlappable too.
    """
    ax = axis or _TP_AXIS
    n = axis_size(ax)
    if n == 1:
        return mm(x)
    i = jax.lax.axis_index(ax)
    perm = [(p, (p + 1) % n) for p in range(n)]
    buf = x
    ys, owners = [], []
    for k in range(n):
        # mm(buf) and ppermute(buf) both depend only on buf: independent
        # ops the latency-hiding scheduler overlaps
        ys.append(mm(buf))
        owners.append((i - k) % n)  # ring flows +1, so we hold shard i-k's x
        if k < n - 1:
            buf = jax.lax.ppermute(buf, ax, perm)

    def assemble(*chunks):
        c = chunks[0].shape[out_seq_dim]
        shape = list(chunks[0].shape)
        shape[out_seq_dim] = c * n
        out = jnp.zeros(shape, chunks[0].dtype)
        for y, o in zip(chunks, owners):
            out = jax.lax.dynamic_update_slice_in_dim(
                out, y, o * c, out_seq_dim)
        return out

    return jax.tree.map(assemble, *ys)


def ring_matmul_rs(h, mm, axis: Optional[str] = None, seq_dim: int = 1):
    """``psum_scatter(mm(h))`` (row-parallel close into SP layout) as a
    ring of partial matmuls.

    ``h``: the full-sequence activation ``[B, S, F_local]`` held
    per-shard as partial features; ``mm`` maps a sequence chunk to its
    (partial) product ``[B, S/n, D]`` and must be row-wise in the
    sequence.  Each ring step adds the local shard's contribution for one
    chunk to the accumulator travelling the ring; after ``n`` steps shard
    ``i`` holds chunk ``i`` fully reduced — the TP reduction and the SP
    scatter in one decomposition, with each hop's ppermute independent of
    that step's partial matmul.
    """
    ax = axis or _TP_AXIS
    n = axis_size(ax)
    if n == 1:
        return mm(h)
    i = jax.lax.axis_index(ax)
    S = h.shape[seq_dim]
    if S % n != 0:
        raise ValueError(f"seq dim {S} not divisible by TP size {n}")
    c = S // n
    perm = [(p, (p + 1) % n) for p in range(n)]

    def chunk(j):
        return jax.lax.dynamic_slice_in_dim(h, j * c, c, seq_dim)

    # chunk j's partial sum starts at shard (j+1)%n and travels +1 each
    # step, collecting every shard's contribution; it lands home at shard
    # j after n-1 hops.  Shard i therefore works on chunk (i-1-k)%n at
    # step k.
    acc = mm(chunk((i - 1) % n))
    for k in range(1, n):
        acc = jax.lax.ppermute(acc, ax, perm)
        acc = acc + mm(chunk((i - 1 - k) % n))
    return acc
