"""Latent (MLA) paged attention as a Pallas TPU kernel: the absorbed form
over a block pool of latent rows.

A latent-attention layer caches ONE row a position, ``[c | k_rope]``
(``W = latent + rope`` wide), shared by every head: it is each head's key,
and its first ``dc = latent`` columns are each head's value
(``models/hybrid.py latent_attention_mixer``; the up-projections are applied
outside, as two matmuls batched over heads).  So a slot's ``H`` query heads
are ``H`` ROWS of one attention problem over one key, and a pool block is
fetched ONCE and used twice: ``s = q c^T`` over all ``W`` columns, ``o += p
c[:, :dc]`` over the first ``dc``.  The existing GQA kernel run as MQA with
the pool passed as K and as V computes the same and reads every block twice
(tests/test_mla_attention.py holds the two equal; PERF.md section 6, PR 30,
times them).

The pool lies ``[L, nb, 1, W, bs]``: a block is the TRANSPOSE of its
``bs`` rows, positions along the lanes.  ``W`` = 576 is no multiple of the
128 lanes: XLA:TPU lays a ``[..., 128, 576]`` array out with the 128 minor
of its own accord, Mosaic takes operands row-major only, and every call
would pay a copy of the whole pool between the two (3.4 GB of temporaries,
compiled for a described v5e before any chip run: PERF.md section 6, PR 30).
Declared transposed, the array is dense (576 = 36 x 16 sublanes) in the one
layout both want, ``s = q c^T`` is a plain product against the block as it
lies, and ``o += p c[:, :dc]`` contracts the lanes of both operands, the
``q k^T`` form the MXU takes natively.  The unit axis keeps the K/V pool's
rank: the block tables, the allocator, copy-on-write and migration see what
they always saw, blocks along dim 1.
Grid ``(slot, row tile, kv-step)``; the fetch rule, the online softmax and
the parameters' table are ``ops/paged_attention.py``'s.  A decode call
(``S_in = 1``) is one row tile of ``H`` rows a slot, named ``mla_decode`` in
the device trace; a prefill chunk's ``H x chunk`` rows go in tiles of
:data:`_ROW_TILE`, each of which walks the slot's live blocks again
(``mla_chunk``).  Rows are head-major (row ``h * S_in + s`` sits at position
``offset + s``), so a tile's mask needs only ``row % S_in``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _out_struct
from .paged_attention import (
    _LANES,
    NEG_INF,
    _ROWS_PER_STEP,
    _accumulate,
    _stacked,
    _step_params,
    fetched_block,
)

#: Query rows one grid step carries.  A decode call's ``H`` rows fit one
#: tile; a chunk's ``H x chunk`` are cut into tiles of this many, each with
#: its ``[rows, dc]`` float32 accumulator in VMEM (2 MB at 1024 x 512).
_ROW_TILE = 1024

#: Scoped VMEM a call may take: a chunk tile's accumulator, its query and
#: output tiles twice over, ``2 x fetch_width`` blocks and a key tile's
#: float32 scores come to more than the compiler's default of 16 MB at the
#: cell's size (a v5e core has 128).
_VMEM_LIMIT = 40 << 20

#: ``(fetch_width, group)``: pool blocks a grid step streams, and how many
#: of them make one key tile of the online softmax, for a call of one small
#: row tile (decode, verify) and for a chunk's row tiles.  MEASURED on the
#: v5e (PR 30, PERF.md section 6; 128 slots x 64 heads over a pool of 4097
#: blocks of 128 x 576 bf16 at a mean context of 1.9k, ms a pass of 5
#: layers): (8, 1) 8.03, (8, 2) 5.64, (8, 4) 4.68, (8, 8) 4.38, (16, 8)
#: 4.35, (32, 4) 4.00, (32, 8) 3.67: a key tile of one block serialises two
#: small products and a softmax step a block, and a slot's table in one grid
#: step saves the steps' fixed price, as in ops/paged_attention.py.  The
#: chunk call (8 slots x 64 x 256 rows, ms a call): tile 512 (8, 1) 7.17,
#: (8, 4) 4.00, (8, 8) 4.30; tile 1024 (8, 4) 3.57, (8, 8) 3.95; tile 256
#: (8, 4) 4.96.
_MLA_DECODE = (32, 8)
_MLA_CHUNK = (8, 4)


def _kernel(tab_ref, off_ref, lay_ref, q_ref, *refs,
            S_in, bs, dc, sm_scale, fetch_width, group, rows):
    """Grid ``(slot b, row tile t, kv-step j)``; ``refs``: the
    ``fetch_width`` latent blocks of the step, the output ref, the (acc, m,
    l) scratch carried across j.  ``group`` fetched blocks make ONE key
    tile of the online softmax: their score products are independent and
    issue back to back, the running maximum, the sum and the ``[rows, dc]``
    accumulator are touched once a tile, and so are the value products.  A
    tile whose first block is live runs whole: a dead block in it holds
    some block's rows (what it last fetched, perhaps the NULL block's),
    every one of them behind the queries' positions: the mask takes them
    out of the scores, and as VALUES the columns behind the call's last
    position are zeroed, so that nothing a live slot does not own, and
    nothing stale in its own last block, reaches the output even as 0 x
    NaN."""
    kv_refs = refs[:fetch_width]
    o_ref = refs[fetch_width]
    acc_ref, m_ref, l_ref = refs[fetch_width + 1:]
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    off = off_ref[b]
    hi = (off + S_in + bs - 1) // bs  # live blocks of this slot

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [rows, W]
    qpos = off + (t * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, group * bs), 0)) % S_in

    for i0 in range(0, fetch_width, group):
        blk = j * fetch_width + i0

        @pl.when(blk < hi)
        def _compute(i0=i0, blk=blk):
            # [W, bs] each: the keys, and in [:dc] the values
            cts = [kv_refs[i0 + g][0, 0, 0] for g in range(group)]
            s = jnp.concatenate(
                [jnp.dot(q, ct, preferred_element_type=jnp.float32)
                 for ct in cts], axis=-1)
            kpos = blk * bs + jax.lax.broadcasted_iota(
                jnp.int32, (rows, group * bs), 1)

            def pv(p):
                p = p.astype(q.dtype)
                out = 0.0
                for g, ct in enumerate(cts):
                    written = (blk + g) * bs + jax.lax.broadcasted_iota(
                        jnp.int32, (dc, bs), 1) < off + S_in
                    out += jax.lax.dot_general(
                        p[:, g * bs:(g + 1) * bs],
                        jnp.where(written, ct[:dc], 0),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                return out

            _accumulate(s * sm_scale, kpos <= qpos, pv,
                        acc_ref, m_ref, l_ref)

    @pl.when(j == (hi - 1) // fetch_width)
    def _write():
        o_ref[0] = (acc_ref[...] / l_ref[..., :1]).astype(o_ref.dtype)


def mla_paged_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    offsets,
    *,
    latent: int,
    sm_scale: float,
    layer=None,
    fetch_width: Optional[int] = None,
    row_tile: Optional[int] = None,
    group: Optional[int] = None,
) -> jnp.ndarray:
    """Absorbed queries ``q`` [B, H, S_in, W] against each slot's latent
    rows in layer ``layer`` of ``pool`` [L, nb, 1, W, bs] (``None``: one
    layer's [nb, 1, W, bs]): softmax(``q . row`` x ``sm_scale``, causal by
    position) over the rows, times their first ``latent`` columns.
    ``tables`` [B, max_blocks], ``offsets`` scalar or [B], as
    :func:`~.paged_attention.paged_decode_attention`.  Returns [B, H, S_in,
    latent] in ``q.dtype``."""
    B, H, S_in, W = q.shape
    pool, _, lay = _stacked(pool, pool, layer)
    _L, _nb, one, Wp, bs = pool.shape
    if one != 1 or Wp != W:
        raise ValueError(
            f"a latent pool is [L, nb, 1, {W}, bs], got {pool.shape}")
    mb = tables.shape[-1]
    offs = jnp.asarray(offsets, jnp.int32)
    if offs.ndim == 0:
        offs = jnp.broadcast_to(offs, (B,))
    R = H * S_in
    small = R <= _ROWS_PER_STEP  # a few rows a head: decode, verify
    fw0, group0 = _MLA_DECODE if small else _MLA_CHUNK
    fw, pad_to = _step_params(
        mb, fw0 if fetch_width is None else fetch_width, None)
    group = max(1, min(int(group or group0), fw))
    fw = -(-fw // group) * group
    rows = min(int(row_tile or _ROW_TILE), -(-R // pad_to) * pad_to)
    tiles = -(-R // rows)
    qr = q.reshape(B, R, W)
    if tiles * rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, tiles * rows - R), (0, 0)))

    def qidx(b, t, j, tab, off, lay):
        return (b, t, 0)

    def kvidx(b, t, j, tab, off, lay, i=0):
        blk, _ = fetched_block(tab, off, b, 0, j, i, S_in=S_in, bs=bs, fw=fw)
        return (lay[0], blk, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, tiles, -(-mb // fw)),
        in_specs=[pl.BlockSpec((1, rows, W), qidx)] + [
            pl.BlockSpec((1, 1, 1, W, bs), functools.partial(kvidx, i=i))
            for i in range(fw)],
        out_specs=pl.BlockSpec((1, rows, latent), qidx),
        scratch_shapes=[
            pltpu.VMEM((rows, latent), jnp.float32),  # acc
            pltpu.VMEM((rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((rows, _LANES), jnp.float32),  # l
        ],
    )
    kernel = functools.partial(
        _kernel, S_in=S_in, bs=bs, dc=latent, sm_scale=float(sm_scale),
        fetch_width=fw, group=group, rows=rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((B, tiles * rows, latent), q.dtype, q),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="mla_decode" if S_in == 1 else "mla_chunk",
    )(tables.astype(jnp.int32), offs, lay, qr, *([pool] * fw))
    return out[:, :R].reshape(B, H, S_in, latent)


def mla_gather_attention(q, pool, tables, offsets, *, latent: int,
                         sm_scale: float, layer=None) -> jnp.ndarray:
    """The same through a gathered dense view of each slot's rows: the
    parity oracle and the CPU path, O(max context) a call."""
    B, _H, S_in, _W = q.shape
    blocks = (pool[tables] if layer is None else pool[layer, tables])[:, :, 0]
    rows = blocks.swapaxes(2, 3).reshape(B, -1, blocks.shape[2])  # [B, T, W]
    s = jnp.einsum("bhsw,btw->bhst", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    offs = jnp.broadcast_to(jnp.asarray(offsets, jnp.int32), (B,))
    qpos = offs[:, None] + jnp.arange(S_in)[None, :]      # [B, S_in]
    keep = jnp.arange(rows.shape[1])[None, None, :] <= qpos[..., None]
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,btc->bhsc", p.astype(q.dtype),
                      rows[..., :latent],
                      preferred_element_type=jnp.float32).astype(q.dtype)
