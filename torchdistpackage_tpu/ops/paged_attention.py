"""Paged decode attention as a Pallas TPU kernel (vLLM PagedAttention
lineage): walk the per-slot block table *inside* the kernel.

The serving engine's gather path (serving/paged_cache.py ``gather_kv``)
materializes every slot's blocks into a contiguous ``[B, Hkv,
max_blocks*bs, hd]`` view before the dense ``_cached_attention`` — O(max
context) HBM read AND written per decode tick, whatever the slot's actual
length, plus an f32 upcast temp of the same size on the int8 pool.  This
kernel removes that round trip.  The block table, the per-slot offsets and
the layer of the stacked pool are scalar-prefetched, and the table is
walked in one of two ways, chosen from the call's shape alone
(:func:`decode_walk`).  Either way a program runs online-softmax flash
accumulation with per-row position masking, per-tick attention HBM traffic
is the live blocks', whole, and nothing else, and VMEM per program is
O(block): what opens 32k+ serving contexts (docs/long_context.md).

The SMALL shape, a handful of query rows a head (decode, the K+1 verify
rows: all of a program's ``hb x rows`` within one 128-row tile), walks the
table IN the kernel (:func:`_walk_kernel`): grid ``(slot, kv-head group)``,
the pools left in HBM, and a loop over the slot's LIVE blocks in key tiles
of ``T`` blocks, tile ``t + 1`` copied into one half of a VMEM buffer while
tile ``t`` is computed from the other: one DMA a live block and side, one
softmax step a tile, and nothing evaluated, fetched or waited for on behalf
of a dead table column.  The pool lies ``[L, nb, Hkv, bs, hd]``, the heads
of one block contiguous, so a copy carries all ``hb`` heads of a program.

A prefill chunk's hundreds of rows a head, and an int8 pool, walk the GRID
(:func:`_kernel`): ``(slot, kv-head, kv-step)``, a step's blocks ONE key tile
and one softmax step (:func:`chunk_tile`), fetched through ``BlockSpec``
operands whose index map is the table; a sub-block past the last live block
asks for the block it already holds (:func:`fetched_block`): no copy.

One entry point covers every serving shape:

- ``S_in = 1`` ordinary decode, ``S_in = K+1`` the speculative verify
  step, ``S_in = chunk`` chunked prefill — all the same kernel, so both
  compiled engine programs ride it;
- scalar or ``[B]``-vector offsets (each slot at its own depth);
- GQA: q heads grouped per KV head OUTSIDE the kernel (a reshape, not a
  repeat) — a KV block is fetched once per group;
- sliding-window masking (Mistral semantics, matching
  ``_cached_attention``);
- int8 pools: ``(q8, scale)`` block pairs are dequantized IN-REGISTER —
  the scale folds into the scores (k) / probabilities (v) exactly as the
  gather path folds it, but the f32 gathered view is never materialized,
  extending the EQuARX thesis (PAPERS.md 2506.17615 — keep quantized
  bytes quantized until the compute that consumes them) from wire
  collectives to the KV-cache read path.

Numerics: scores and the online softmax run in f32 (matching the gather
path's f32 softmax); the accumulation ORDER differs (blockwise online
rescale vs one full-row softmax), so logits agree to float tolerance and
greedy tokens bit-match the gather goldens (tests/test_paged_attention.py
locks dense, GQA, sliding-window, vector offsets, and the K+1 verify
shape).  The gather path stays in-tree as the parity oracle.

Two things a model may ask of either walk, each absent from every other
model's program.  UNEQUAL WIDTHS: value heads narrower than key heads (192
over 128): the pool's K leaf then lies transposed, ``[L, nb, Hkv, hd,
bs]`` (serving/paged_cache.py "Unequal widths"), a key tile is ``[hd,
keys]`` and a score ``q [rows, hd] . k [hd, keys]``; the accumulator and the
output are the values' width.  A SINK: a learned scalar a query head that
stands in every row's softmax as one more column and gives no value, ``p_j
= exp(s_j) / (exp(sink_h) + sum_i exp(s_i))``: the online softmax starts at
``(m, l, acc) = (sink_h, 1, 0)`` where it started at ``(-inf, 0, 0)``
(:func:`_start`), the sinks a fourth scalar-prefetch operand.

On CPU the kernel runs in Pallas interpreter mode automatically (same
``_interpret`` switch as ops/flash_attention.py), so every test exercises
the identical code path the TPU compiles.

Tuning: both walks' key tiles, ``hb`` and a program's rows follow from the
shape (:func:`call_walk`); ``q_pad_to`` (the q rows' padding multiple: the
K+1 verify shape lands at awkward row counts like G*(K+1)) comes from the
per-chip table :data:`_PAGED_PARAMS` (tools/flash_tune.py ``--paged``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _out_struct

NEG_INF = -1e30  # finite "minus infinity": avoids (-inf) - (-inf) NaNs

_LANES = 128  # m/l scratch keeps a full lane dim for layout friendliness

#: A program carries several KV heads only while all its query rows fit one
#: 128-row tile (decode: 8 heads x 8 rows; a chunk's 1,024 rows: 1 head).
_ROWS_PER_STEP = 128

#: What a program of the GRID's walk may hold, against the scoped VMEM its call
#: asks for (:data:`_GRID_VMEM_LIMIT`; a v5e has 128 MB, a call 16 MB unless
#: it asks).  Two parts grow: with a program's ROWS its q and out blocks
#: (twice over) and the float32 (acc, m, l) scratch, 2.5 KB a row of 128 bf16
#: lanes; with ROWS x KEYS the float32 scores and the probabilities of one
#: key tile, ~5.5 B a score.  :data:`_PROGRAM_ROWS` bounds the first: a KV
#: head's query rows up to it are one program, past it they are dealt in
#: whole query heads to the fewest programs that come under it
#: (:func:`head_split`; each fetches the head's blocks again).
#: :data:`_TILE_SCORE_BYTES` bounds the second (:func:`chunk_tile`).
#: MEASURED (PR 42, compiled alone for a described v5e, MB asked): 1,024 rows
#: x 768 keys 7.5, 2,048 x 768 14.66, 2,048 x 1,280 19.59, 4,096 x 896 33.59;
#: the step a block of PR 41 asked for 16.27 at 2,048 rows, 17.4 at 4,096.
_PROGRAM_ROWS = 4096
_TILE_SCORE_BYTES = 24 << 20
_GRID_VMEM_LIMIT = 64 << 20

#: VMEM the K + V blocks a program holds twice over may take (bytes): ``2 x 2
#: x blocks x heads x block`` (a key tile's, or ``fetch_width``) stays under it.
_KV_VMEM_BUDGET = 8 << 20

#: Kernel parameters by device_kind substring.  ``q_pad_to`` = the q rows'
#: padding multiple (G*(K+1) verify rows are rarely tile-aligned); ``fetch_width``
#: = pool blocks a step of the ring hop (:func:`_cp_kernel`): both walks here take
#: their key tile from the shape.  The v5e row is MEASURED there (PR 29, ``tools/
#: flash_tune.py --paged --shape mistral7b.decode``).  The decode walk's ``T`` (PR
#: 34, ``--shape zaya1.reason``; ms a call, decode): 1 0.366, 2 0.236, 4 0.175, 6
#: 0.159, 10 0.152, 20 0.151.  The GRID's tile (PR 42, a chunk's call alone, ms; a
#: step a block -> tiles of n blocks): mistral7b.decode's 8 x 256 rows over 6
#: columns 0.780 -> 1 0.798, 3 0.559, 6 0.432; zaya1.reason's 4 x 256 over 20 0.278
#: -> 3 0.157, 5 0.116, 6 0.127, 7 0.114, 10 0.115; nemotron3s.decode's 2,048 rows
#: over 6 0.173 -> 1 0.158, 2 0.133, 3 0.114, 6 0.091; trinitymini.mixedlen's 4 x
#: 4,096 rows: PERF.md section 6, PR 42 (rows a program x tile).  cpu: interpreter.
_PAGED_PARAMS = (
    ("v5 lite", {"fetch_width": 6, "q_pad_to": 8}),
    ("v5e", {"fetch_width": 6, "q_pad_to": 8}),
    ("cpu", {"fetch_width": 1, "q_pad_to": 8}),
)


def paged_params_for(device_kind: str) -> dict:
    dk = device_kind.lower()
    for sub, params in _PAGED_PARAMS:
        if sub in dk:
            return dict(params)
    raise ValueError(
        f"paged_attention: no parameter row for device_kind={device_kind!r}; "
        "run tools/flash_tune.py --paged on this chip and add a "
        "_PAGED_PARAMS row")


def default_paged_params() -> dict:
    """``{fetch_width, q_pad_to}`` for the attached chip, from
    :data:`_PAGED_PARAMS`."""
    return paged_params_for(jax.devices()[0].device_kind)


def _step_params(mb: int, fetch_width: Optional[int],
                 q_pad_to: Optional[int]) -> Tuple[int, int]:
    """``(fetch_width, q_pad_to)`` of a call over ``mb`` table columns: the
    caller's, else the attached chip's row (one row serves the decode, the
    verify and the chunk rows: PR 29's measurement)."""
    params = default_paged_params()
    fw = int(fetch_width if fetch_width is not None else
             params["fetch_width"])
    pad_to = int(q_pad_to if q_pad_to is not None else params["q_pad_to"])
    return max(1, min(fw, mb)), pad_to


def resolve_attn_impl(impl: Optional[str]) -> str:
    """``'auto'``/None -> ``'pallas'`` on TPU, ``'gather'`` elsewhere (the
    interpreter-mode kernel is correct on CPU but slow — tests opt in
    explicitly).  Explicit values pass through validated."""
    if impl in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "gather"
    if impl not in ("pallas", "gather"):
        raise ValueError(
            f"attn_impl must be 'pallas', 'gather' or 'auto', got {impl!r}")
    return impl


def _compiler_params(vmem_limit_bytes: Optional[int] = None):
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def _heads_per_step(Hkv: int, rows: int, fw: int, block_bytes: int) -> int:
    """KV heads one grid step carries, from the shape: as many as divide
    ``Hkv`` while the step's query rows stay within one 128-row tile
    (:data:`_ROWS_PER_STEP`) and its double-buffered K + V blocks within
    :data:`_KV_VMEM_BUDGET`.  A chunk's hundreds of rows a head give 1: the
    grid ``(slot, kv-head, kv-step)`` of one head a step."""
    hb = 1
    for cand in range(2, Hkv + 1):
        if Hkv % cand == 0 and cand * rows <= _ROWS_PER_STEP and (
                2 * 2 * fw * cand * block_bytes <= _KV_VMEM_BUDGET):
            hb = cand
    return hb


def window_binds(window: Optional[int], mb: int, bs: int) -> bool:
    """Whether a window can lie short of what a table of ``mb`` columns
    holds.  Where it can, both walks START at the window
    (:func:`first_column`) and the call carries a kernel name of its own
    (``swa_decode`` / ``swa_chunk``); where it cannot (no window, or one as
    wide as the table: a mask that never takes a key out), the walk is the
    one from column 0, operation for operation."""
    return window is not None and window < mb * bs


def first_column(off, window: int, bs: int):
    """The table column that holds the first key inside the window of a
    call's FIRST row, at position ``off``: keys in ``(off - window, off]``.
    Every column before it lies wholly behind every row's window (a later
    row's starts later), so neither walk fetches it or multiplies by it;
    inside it the mask does the rest."""
    return jnp.maximum(off - (window - 1), 0) // bs


def window_columns(window: int, S_in: int, bs: int) -> int:
    """The most table columns that ``S_in`` rows' windows reach over, at
    the worst alignment: positions ``[off - window + 1, off + S_in)``."""
    return (window + S_in - 3) // bs + 2


def head_split(groups: int, S_in: int) -> int:
    """The programs that a KV head's ``groups x S_in`` query rows are dealt
    to: 1 up to :data:`_PROGRAM_ROWS`; past it the fewest whole query heads'
    worth that brings a program's rows under it."""
    return next((d for d in range(1, groups + 1) if groups % d == 0
                 and groups * S_in // d <= _PROGRAM_ROWS), groups)


def walked_columns(window: Optional[int], mb: int, S_in: int, bs: int) -> int:
    """The table columns a call's walk reaches over: all ``mb``, or a
    window's (:func:`window_columns`) where it binds."""
    if not window_binds(window, mb, bs):
        return mb
    return min(mb, window_columns(window, S_in, bs))


def fetched_block(tab, off, b, h, j, i, *, S_in: int, bs: int, fw: int,
                  window: Optional[int] = None):
    """``(pool block, head group)`` that sub-block operand ``i`` asks for at
    grid step ``(b, h, j)``: the index map's rule, as a pure function of the
    table and the offsets (refs, traced or numpy arrays alike).  ``window``
    (one that binds, :func:`window_binds`): step 0 stands at the slot's
    :func:`first_column`, not at column 0.

    Live (``j*fw + i`` within the slot's live blocks): the table's entry.
    Dead: what the operand already HOLDS, so that the pipeline, which skips
    a copy whose block index did not change, fetches nothing: its own last
    live block of this slot, or, if it was never live here, head group 0 of
    pool block 0 (any valid block: the compute of a dead sub-block is
    skipped), which stays the same index from head to head and slot to
    slot."""
    mb = tab.shape[-1]
    hi1 = jnp.minimum((off[b] + S_in + bs - 1) // bs, mb) - 1
    if window is not None:   # the operand's columns count from the window
        i = i + first_column(off[b], window, bs)
    blk = j * fw + i
    own_last = i + fw * (jnp.maximum(hi1 - i, 0) // fw)
    col = jnp.where(blk <= hi1, blk, own_last)
    live_here = i <= hi1
    return (jnp.where(live_here, tab[b, jnp.minimum(col, mb - 1)], 0),
            jnp.where(live_here, h, 0))


def _start(acc_ref, m_ref, l_ref, sink_ref=None, head0=None, gp=0, S_in=0):
    """The online softmax before its first key tile: ``(m, l, acc) = (-inf,
    0, 0)``.  With a sink (``sink_ref`` [H] float32 in SMEM) a row starts
    from its query head's one more column instead, ``(sink_h, 1, 0)``: the
    rows of a program are group-major, ``gp`` query heads of ``S_in`` rows
    a KV head, the first of them head ``head0``, so each head's scalar goes
    to a slab of ``S_in`` rows (padding rows behind them keep ``-inf``)."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    if sink_ref is None:
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        return
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for kvh in range(m_ref.shape[0]):
        for g in range(gp):
            m_ref[kvh, pl.ds(g * S_in, S_in), :] = jnp.full(
                (S_in, m_ref.shape[2]), sink_ref[head0 + kvh * gp + g],
                jnp.float32)


def _sunk(kernel):
    """``kernel`` as a call with a sink hands it over: the sinks are the
    fourth scalar-prefetch operand, the body's ``sink_ref``."""
    def body(tab_ref, off_ref, lay_ref, sink_ref, *refs):
        return kernel(tab_ref, off_ref, lay_ref, *refs, sink_ref=sink_ref)
    return body


def _accumulate(s, keep, pv, acc_ref, m_ref, l_ref):
    """One KV block's online-softmax step on the (acc, m, l) scratch:
    ``s`` the scaled f32 scores ``[..., rows, bs]`` (a leading head axis or
    none), ``keep`` their mask, ``pv(p)`` the f32 ``[..., rows, hd]`` product
    of the block's probabilities with its values."""
    s = jnp.where(keep, s, NEG_INF)
    m = m_ref[..., :1]
    l = l_ref[..., :1]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_ref[...] = jnp.broadcast_to(
        l * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + pv(p)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


#: Keys one key tile of the decode walk holds at most (MEASURED at blocks of
#: 128, see :data:`_PAGED_PARAMS`: ten blocks): a wider tile saves no more
#: softmax steps than its dead tail's products cost, and its float32 scores
#: ``[hb, rows, keys]`` grow with it.
_KV_TILE_KEYS = 1280

#: Keys one key tile of the GRID's walk holds at most (MEASURED at a chunk's
#: 1,024-4,096 rows a program, PR 42: the gain over a step a block is had by
#: six blocks of 128, seven to ten read the same at 1,024 rows, ten read 19%
#: WORSE than eight at 2,048).
_CHUNK_TILE_KEYS = 1024


def chunk_tile(hb: int, rows: int, cols: int, bs: int) -> int:
    """Pool blocks of one key tile of the GRID's walk, which is what a grid
    step fetches, from the shape alone: the walk's ``cols`` columns in the
    fewest steps whose tile stays within :data:`_CHUNK_TILE_KEYS` keys and
    whose float32 scores and probabilities ``[hb x rows, keys]`` (~5.5 B a
    score) within :data:`_TILE_SCORE_BYTES`, the steps then made EQUAL: 21
    columns under a cap of eight blocks are three tiles of 7, not 8 + 8 + 5
    (a dead sub-block of a live tile is multiplied and masked like a live
    one).  A shape that fits nothing wider than a block keeps a step a
    block."""
    cap = max(1, min(_CHUNK_TILE_KEYS,
                     2 * _TILE_SCORE_BYTES // (11 * hb * rows)) // bs)
    steps = -(-cols // cap)
    return -(-cols // steps)


def decode_walk(Hkv: int, rows: int, mb: int, fw: int, bs: int,
                block_bytes: int, quantized: bool = False) -> Tuple[int, int]:
    """``(hb, T)`` of a call, from its shape alone: the KV heads a program
    carries (:func:`_heads_per_step`) and the pool blocks of one key tile.
    ``T`` > 0 names the in-kernel walk over live blocks
    (:func:`_walk_kernel`): the SMALL shape, a handful of query rows a head
    (decode, the K+1 verify rows), where all ``hb x rows`` query rows fit
    one 128-row tile (:data:`_ROWS_PER_STEP`); ``T`` is as many of the
    table's columns, up to :data:`_KV_TILE_KEYS` keys, as keep the two
    halves of the K and V tile buffers within :data:`_KV_VMEM_BUDGET`
    (``block_bytes``: one head's ``bs`` rows).  ``T`` = 0:
    a prefill chunk's hundreds of rows a head, and an int8 pool, keep the
    grid's walk over table columns (:func:`_kernel`), ``fw`` blocks a step."""
    if quantized or rows > _ROWS_PER_STEP:
        return _heads_per_step(Hkv, rows, fw, block_bytes), 0
    hb = _heads_per_step(Hkv, rows, 1, block_bytes)
    fit = _KV_VMEM_BUDGET // (2 * 2 * hb * block_bytes)
    return hb, max(1, min(_KV_TILE_KEYS // bs, mb, fit))


def call_walk(R: int, Hkv: int, mb: int, bs: int, block_bytes: int,
              quantized: bool = False, fetch_width: Optional[int] = None,
              q_pad_to: Optional[int] = None) -> Tuple[int, int, int, int]:
    """``(rows, fw, hb, T)`` of a call of ``R`` query rows a KV head (one of
    its :func:`head_split` programs') over ``mb`` walked columns on the
    attached chip: the rows padded to the chip's multiple
    (:func:`_step_params`), ``hb`` and ``T`` from :func:`decode_walk`, and,
    where the grid walks (``T`` = 0), ``fw`` = the blocks of its one key tile
    a step (:func:`chunk_tile`).  A caller's own ``fetch_width`` (the
    tuner's, the tests') is the blocks fetched at a time in either walk: the
    key tile's in both.  What the wrapper runs, what
    :func:`modeled_attend_temp_bytes` counts, what the tuner prints and what
    the engine writes on its ``tdp:engine.init.pool`` span."""
    fw, pad_to = _step_params(mb, fetch_width, q_pad_to)
    rows = -(-R // pad_to) * pad_to
    if fetch_width is not None:
        hb, T = decode_walk(Hkv, rows, mb, fw, bs, block_bytes, quantized)
        return rows, fw, hb, fw if T else 0
    # hb's K + V blocks: at the widest tile a grid step takes
    hb, T = decode_walk(Hkv, rows, mb, min(mb, max(1, _CHUNK_TILE_KEYS // bs)),
                        bs, block_bytes, quantized)
    return rows, fw if T else chunk_tile(hb, rows, mb, bs), hb, T


def shape_walk(groups: int, S_in: int, Hkv: int, mb: int, bs: int,
               block_bytes: int, window: Optional[int] = None,
               quantized: bool = False, fetch_width: Optional[int] = None,
               q_pad_to: Optional[int] = None) -> Tuple[int, ...]:
    """``(split, cols, rows, fw, hb, T)`` of a call of ``groups x S_in``
    query rows a KV head over a table of ``mb`` columns, as the wrapper asks
    :func:`call_walk`: the rows of ONE of the head's :func:`head_split`
    programs over the columns the walk reaches (:func:`walked_columns`)."""
    split = head_split(groups, S_in)
    cols = walked_columns(window, mb, S_in, bs)
    return (split, cols) + call_walk(
        groups * S_in // split, Hkv, cols, bs, block_bytes, quantized,
        fetch_width, q_pad_to)


def _walk_kernel(
    tab_ref, off_ref, lay_ref, q_ref, k_hbm, v_hbm, o_ref,
    kbuf, vbuf, sem, par_ref, acc_ref, m_ref, l_ref,
    *, S_in, bs, mb, window, sm_scale, rows, hb, T, bound, kt=False,
    sink_ref=None, gp=0,
):
    """The decode shape's walk.  Grid ``(slot b, kv-head group h)``, run in
    order; the pools stay in HBM and program ``(b, h)`` loops over the
    slot's LIVE blocks in key tiles of ``T``: tile ``t`` lies in one half of
    ``kbuf`` / ``vbuf`` ``[2, hb, T x bs, hd]`` while the copies of tile
    ``t + 1`` (after the last, of the NEXT program's first tile) fill the
    other, one DMA a live block and side, none for a dead one.  ``par_ref``
    (SMEM) carries from program to program which half the first tile is in.
    A tile is ONE online-softmax step: one batched score product over its
    ``T x bs`` keys, one max / exp / rescale, one value product.  A dead
    block inside a live tile holds whatever the buffer held: its keys lie
    behind every query position, so the mask takes them out of the scores,
    and as values the rows behind the call's last position are zeroed (so
    are the rows of the slot's own last block that nobody wrote yet):
    nothing reaches the output even as 0 x NaN.

    ``bound`` (static; :func:`window_binds`): the walk's block 0 is the
    slot's :func:`first_column` and not the table's column 0, so a block
    that lies wholly behind the window is not fetched, waited for or
    multiplied by, whatever its table column names.  Without it the body is
    the one from column 0, operation for operation.

    ``kt`` (static): the K pool lies transposed, ``[L, nb, Hkv, hd, bs]``,
    and so does its tile, ``kbuf`` ``[2, hb, hd, T x bs]``: a block is
    copied beside the last along the LANES.  ``sink_ref`` / ``gp``:
    :func:`_start`."""
    b, h = pl.program_id(0), pl.program_id(1)
    nh = pl.num_programs(1)
    lay = lay_ref[0]

    def column(b, j):
        """The table column of the j-th block of slot ``b``'s walk."""
        return j + first_column(off_ref[b], window, bs) if bound else j

    def live_blocks(b):
        """The blocks of slot ``b``'s walk."""
        live = jnp.minimum((off_ref[b] + S_in + bs - 1) // bs, mb)
        return live - column(b, 0) if bound else live

    def tile_copies(b, h, t, half, act):
        """Start or wait for (``act``) the copies of slot ``b``'s tile ``t``
        of head group ``h`` into buffer half ``half``: a loop over the
        tile's live blocks.  (``T`` unrolled copies behind a test each ran
        no faster and cost ``T`` times the trace:
        :func:`paged_decode_attention` on what a trace costs.)"""
        def block(i, carry):
            src = (lay, tab_ref[b, column(b, t * T + i)], pl.ds(h * hb, hb))
            dst = (half, slice(None), pl.ds(pl.multiple_of(i * bs, bs), bs))
            kdst = dst[:2] + (slice(None),) + dst[2:] if kt else dst
            for pool, buf, side, to in ((k_hbm, kbuf, 0, kdst),
                                        (v_hbm, vbuf, 1, dst)):
                act(pltpu.make_async_copy(
                    pool.at[src], buf.at[to], sem.at[half, side]))
            return carry

        jax.lax.fori_loop(
            0, jnp.clip(live_blocks(b) - t * T, 0, T), block, None)

    start = lambda c: c.start()
    wait = lambda c: c.wait()

    @pl.when((b == 0) & (h == 0))
    def _first():
        par_ref[0] = 0
        tile_copies(b, h, 0, 0, start)

    off = off_ref[b]
    last = off + S_in  # positions written so far, this call's rows included
    tiles = (live_blocks(b) + T - 1) // T
    par0 = par_ref[0]
    _start(acc_ref, m_ref, l_ref, sink_ref,
           None if sink_ref is None else h * hb * gp, gp, S_in)

    q = q_ref[0]  # [hb, rows, hd]
    # row r covers query position off + (r % S_in) (group-major rows);
    # padded rows past the real R mask everything and are sliced off
    qpos = off + jax.lax.broadcasted_iota(
        jnp.int32, (hb, rows, T * bs), 1) % S_in

    def tile(t, carry):
        half = (par0 + t) % 2

        @pl.when(t + 1 < tiles)
        def _next_tile():
            tile_copies(b, h, t + 1, 1 - half, start)

        @pl.when((t + 1 == tiles) & ((b + 1 < pl.num_programs(0))
                                     | (h + 1 < nh)))
        def _next_program():
            wrap = h + 1 == nh
            tile_copies(jnp.where(wrap, b + 1, b), jnp.where(wrap, 0, h + 1),
                        0, 1 - half, start)

        tile_copies(b, h, t, half, wait)
        k = kbuf[half]  # [hb, T*bs, hd]; transposed: [hb, hd, T*bs]
        v = vbuf[half]
        s = jnp.einsum("hrd,hdk->hrk" if kt else "hrd,hkd->hrk", q, k,
                       preferred_element_type=jnp.float32)
        # the tile's first key position
        pos0 = column(b, t * T) * bs if bound else t * (T * bs)
        kpos = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (hb, rows, T * bs), 2)
        keep = kpos <= qpos
        if window is not None:  # Mistral: key in (qpos - window, qpos]
            keep = keep & (kpos > qpos - window)
        written = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 1) < last
        v = jnp.where(written, v, 0)
        _accumulate(
            s * sm_scale, keep,
            lambda p: jnp.einsum("hrk,hkd->hrd", p.astype(v.dtype), v,
                                 preferred_element_type=jnp.float32),
            acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, tiles, tile, None)
    par_ref[0] = (par0 + tiles) % 2
    # l > 0 for every real row (a query always attends its own position);
    # padded rows divide garbage that is sliced away
    o_ref[0] = (acc_ref[...] / l_ref[..., :1]).astype(o_ref.dtype)


def _kernel(
    tab_ref, off_ref, lay_ref, q_ref, *refs,
    S_in, bs, window, sm_scale, quantized, fetch_width, rows, hb, bound,
    kt=False, sink_ref=None, gp=0,
):
    """Grid ``(slot b, kv-head group h, kv-step j)``, ``hb`` KV heads a
    group, the batch axis of every product in here; ``lay_ref`` (the layer
    of the stacked pool) is read by the index maps alone; ``refs`` carries
    the ``fetch_width`` per-step KV blocks of ``hb`` heads each ((k, v)
    dense or (k8, ks, v8, vs) quantized, sub-block-major), then the output
    ref and the (acc, m, l) online-softmax VMEM scratch carried across j
    steps.  A step's blocks side by side are ONE key tile and one
    online-softmax step: one score product over its ``fetch_width x bs``
    keys, one mask, one max / exp / rescale, one value product (a step a
    block spent more on rescaling the accumulator than on its two
    products).  A dead sub-block of a live tile holds a block fetched
    earlier (:func:`fetched_block`): its key positions lie past every query
    position, so the mask takes them out of the scores, and as values the
    rows behind the call's last position are zeroed (so are the rows of the
    slot's own last block that nobody wrote yet): nothing reaches the output
    even as 0 x NaN.  A step whose first block is dead is skipped whole.
    ``bound`` (static; :func:`window_binds`): step 0 stands at the slot's
    :func:`first_column`, as the index map's, and the grid is only as long
    as a window's columns.  ``kt`` (static): a K block lies transposed,
    ``[hb, hd, bs]``, and a step's blocks stand side by side along the
    lanes.  ``sink_ref`` / ``gp``: :func:`_start`."""
    per = 4 if quantized else 2
    fw = fetch_width
    kv_refs = refs[:fw * per]
    o_ref = refs[fw * per]
    acc_ref, m_ref, l_ref = refs[fw * per + 1:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    off = off_ref[b]
    last = off + S_in  # positions written so far, this call's rows included
    hi = (last + bs - 1) // bs  # live KV blocks for this slot
    col0 = first_column(off, window, bs) if bound else None
    blk0 = j * fw + col0 if bound else j * fw  # the tile's first column

    # the first query head of this program's rows (a sink's index)
    head0 = None if sink_ref is None else pl.program_id(1) * hb * gp

    @pl.when(j == 0)
    def _init():
        _start(acc_ref, m_ref, l_ref, sink_ref, head0, gp, S_in)

    @pl.when(blk0 < hi)
    def _compute():
        def side(n, axis, written=False):
            """Operand ``n`` of the step's blocks side by side along
            ``axis``, their key axis; ``written``: zero behind ``last``."""
            x = jnp.concatenate(
                [kv_refs[per * i + n][0, 0] for i in range(fw)], axis=axis)
            if not written:
                return x
            pos = blk0 * bs + jax.lax.broadcasted_iota(
                jnp.int32, x.shape, axis)
            return jnp.where(pos < last, x, jnp.zeros_like(x))

        # scores [hb, rows, K] = q . k over hd; update [hb, rows, hd] = p . v
        qk = functools.partial(jnp.einsum,
                               "hrd,hdk->hrk" if kt else "hrd,hkd->hrk",
                               preferred_element_type=jnp.float32)
        pv = functools.partial(jnp.einsum, "hrk,hkd->hrd",
                               preferred_element_type=jnp.float32)
        q = q_ref[0]  # [hb, rows, hd]
        if quantized:  # scale rows [hb, 1, K]; an int8 value is never NaN
            k8, ks = side(0, 1), side(1, 2)
            v8, vs = side(2, 1), side(3, 2, written=True)
            s = qk(q.astype(jnp.float32), k8.astype(jnp.float32)) * ks
            upd = lambda p: pv(p * vs, v8.astype(jnp.float32))
        else:
            # [hb, K, hd]; transposed keys: [hb, hd, K]
            k, v = side(0, 2 if kt else 1), side(1, 1, written=True)
            s = qk(q, k)
            upd = lambda p: pv(p.astype(v.dtype), v)
        # row r covers query position off + (r % S_in) (group-major rows);
        # padded rows past the real R mask everything and are sliced off
        qpos = off + jax.lax.broadcasted_iota(
            jnp.int32, (hb, rows, 1), 1) % S_in
        kpos = blk0 * bs + jax.lax.broadcasted_iota(
            jnp.int32, (hb, 1, fw * bs), 2)
        keep = kpos <= qpos
        if window is not None:  # Mistral: key in (qpos - window, qpos]
            keep = keep & (kpos > qpos - window)
        _accumulate(s * sm_scale, keep, upd, acc_ref, m_ref, l_ref)

    @pl.when(j == ((hi - 1 - col0) if bound else (hi - 1)) // fw)
    def _write():
        # l > 0 for every real row (a query always attends its own
        # position); padded rows divide garbage that is sliced away
        o_ref[0] = (acc_ref[...] / l_ref[..., :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "sm_scale", "fetch_width", "q_pad_to"))
def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: Any,
    v_pool: Any,
    tables: jnp.ndarray,
    offsets,
    *,
    layer=None,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    fetch_width: Optional[int] = None,
    q_pad_to: Optional[int] = None,
    sink: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Attention of ``q`` [B, H, S_in, hd] against each slot's paged
    context, walking the block table in-kernel.

    ``k_pool``/``v_pool``: the WHOLE pool ``[L, num_blocks, Hkv, bs, hd]``
    (or its int8 ``(q8 [..., hd], scale [...])`` pair) and ``layer`` (an
    int, traced or not) naming the layer to read: the layer is one more
    scalar-prefetch operand and the index map's leading coordinate, so no
    ``pool[layer]`` is ever materialised.  ``layer=None``: the pools are
    ONE layer's ``[num_blocks, Hkv, bs, hd]``, taken as the one-layer
    stack.  ``tables`` [B, max_blocks] int32 block tables; ``offsets``
    scalar or [B] — slot b's rows sit at positions ``offsets[b] +
    arange(S_in)`` and attend keys at ``kpos <= qpos`` (``window``
    additionally bounds below).  Returns [B, H, S_in, hd] in ``q.dtype``
    — drop-in for the gather path's ``_cached_attention`` output
    (float-tolerance equal; the engine goldens assert token bit parity).
    A pool of UNEQUAL widths (``k_pool`` ``[L, num_blocks, Hkv, hd, bs]``,
    transposed, beside ``v_pool`` ``[.., bs, hv]``): returns [B, H, S_in,
    hv].  ``sink`` [H] float32: one more column of every row's softmax, the
    scalar of the row's query head, which gives no value (module docstring).

    Jitted, the layer an operand: a program whose python-unrolled layers
    call it with one shape traces and lowers the kernel ONCE.  Beside a TPU
    every static index of a kernel body becomes a device constant as it is
    traced: a decode walk with its ~80 copies unrolled took 1.4 s to trace
    there (0.17 s on a CPU), twenty layers' index maps 20 s to lower, all
    inside ``setup_s``.
    """
    B, H, S_in, hd = q.shape
    k_pool, v_pool, lay = _stacked(k_pool, v_pool, layer)
    quantized = isinstance(k_pool, tuple)
    k_arr, v_arr = (k_pool[0], v_pool[0]) if quantized else (k_pool, v_pool)
    _L, nb, Hkv, bs, hv = v_arr.shape
    # unequal widths: the K leaf lies transposed, [L, nb, Hkv, hd, bs]
    kt = k_arr.shape[3:] != v_arr.shape[3:]
    if kt and (quantized or k_arr.shape[3:] != (hd, bs)):
        raise NotImplementedError(
            f"a K leaf {k_arr.shape} beside a V leaf {v_arr.shape}: keys of "
            f"another width than the values lie [.., {hd}, {bs}], bfloat16 "
            f"or float32")
    groups, rem = divmod(H, Hkv)
    if rem:
        raise ValueError(
            f"GQA needs q heads divisible by kv heads, got {H} vs {Hkv}")
    mb = tables.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    offs = jnp.asarray(offsets, jnp.int32)
    if offs.ndim == 0:
        offs = jnp.broadcast_to(offs, (B,))
    # group-major rows: row r = g*S_in + s covers position off + s
    R = groups * S_in
    # a window that can lie short of the table: both walks start at it and
    # reach over its columns alone, under a kernel name of their own; more
    # rows a KV head than one program takes: its query heads go to ``split``
    # programs, each fetching the head's blocks
    bound = window_binds(window, mb, bs)
    # a head's block of K and of V, their mean: what a tile holds of each
    split, cols, rows, fw, hb, T = shape_walk(
        groups, S_in, Hkv, mb, bs, bs * (hd + hv) // 2 * k_arr.dtype.itemsize,
        window, quantized, fetch_width, q_pad_to)
    R //= split
    qr = q.reshape(B, Hkv * split, R, hd)
    if rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - R), (0, 0)))
    name = ("swa_" if bound else "paged_") + (
        "decode" if S_in == 1 else "chunk")
    scalars = (tables.astype(jnp.int32), offs, lay)
    more = {}   # what no call of equal widths and no sink carries
    if kt:
        more["kt"] = True
    if sink is not None:
        scalars += (sink.astype(jnp.float32),)
        more["gp"] = groups // split
    if T:
        return _walk_call(qr, k_pool, v_pool, scalars, more, S_in=S_in,
                          window=window, sm_scale=float(sm_scale), hb=hb,
                          T=T, name=name, bound=bound,
                          )[:, :, :R].reshape(B, H, S_in, hv)

    def qidx(b, h, j, *_):
        return (b, h, 0, 0)

    def kvidx(b, h, j, tab, off, lay, *_, i=0, own_layer=False):
        blk, hg = fetched_block(tab, off, b, h if split == 1 else h // split,
                                j, i, S_in=S_in, bs=bs, fw=fw,
                                window=window if bound else None)
        return (0 if own_layer else lay[0], blk, hg, 0, 0)

    # per sub-block (k, v) / (k8, ks, v8, vs), sub-block-major, each block
    # the hb heads of one pool block: one contiguous copy
    in_specs = [pl.BlockSpec((1, hb, rows, hd), qidx)]
    operands = [qr]
    scales = [_scale_rows(pool[1], lay) if quantized else None
              for pool in (k_pool, v_pool)]
    blocks = ((1, 1, hb, hd, bs) if kt else (1, 1, hb, bs, hd),
              (1, 1, hb, bs, hv))
    for i in range(fw):
        for pool, scale, block in zip((k_pool, v_pool), scales, blocks):
            in_specs.append(pl.BlockSpec(
                block, functools.partial(kvidx, i=i)))
            operands.append(pool[0] if quantized else pool)
            if quantized:
                in_specs.append(pl.BlockSpec(
                    (1, 1, hb, 1, bs),
                    functools.partial(kvidx, i=i, own_layer=True)))
                operands.append(scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, Hkv * split // hb, -(-cols // fw)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, rows, hv), qidx),
        scratch_shapes=[
            pltpu.VMEM((hb, rows, hv), jnp.float32),      # acc
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),  # l
        ],
    )
    kernel = functools.partial(
        _kernel, S_in=S_in, bs=bs, window=window, sm_scale=float(sm_scale),
        quantized=quantized, fetch_width=fw, rows=rows, hb=hb, bound=bound,
        **more)
    out = pl.pallas_call(
        _sunk(kernel) if sink is not None else kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((B, Hkv * split, rows, hv), q.dtype, q),
        compiler_params=_compiler_params(_GRID_VMEM_LIMIT),
        interpret=_interpret(),
        name=name,
    )(*scalars, *operands)
    return out[:, :, :R].reshape(B, H, S_in, hv)


def _walk_call(qr, k_pool, v_pool, scalars, more, *, S_in, window, sm_scale,
               hb, T, name, bound):
    """The ``pallas_call`` of :func:`_walk_kernel` over padded group-major
    rows ``qr`` [B, Hkv, rows, hd] and the stacked pools; ``more``: the
    kernel's ``kt`` (the K pool transposed) and ``gp`` (a call whose fourth
    scalar is the sinks), where the call has either."""
    B, Hkv, rows, hd = qr.shape
    bs, hv = v_pool.shape[3:]
    mb = scalars[0].shape[-1]

    def qidx(b, h, *_):
        return (b, h, 0, 0)

    tile = pltpu.VMEM((2, hb, T * bs, hv), v_pool.dtype)
    ktile = (pltpu.VMEM((2, hb, hd, T * bs), k_pool.dtype)
             if more.get("kt") else tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, Hkv // hb),
        in_specs=[pl.BlockSpec((1, hb, rows, hd), qidx),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hb, rows, hv), qidx),
        scratch_shapes=[
            ktile, tile,                                  # K, V tiles x 2
            pltpu.SemaphoreType.DMA((2, 2)),              # [half, K | V]
            pltpu.SMEM((1,), jnp.int32),                  # first tile's half
            pltpu.VMEM((hb, rows, hv), jnp.float32),      # acc
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),  # l
        ],
    )
    kernel = functools.partial(
        _walk_kernel, S_in=S_in, bs=bs, mb=mb, window=window,
        sm_scale=sm_scale, rows=rows, hb=hb, T=T, bound=bound, **more)
    if "gp" in more:
        kernel = _sunk(kernel)
    # programs run in order: each starts the next one's first copies
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((B, Hkv, rows, hv), qr.dtype, qr),
        compiler_params=params,
        interpret=_interpret(),
        name=name,
    )(*scalars, qr, k_pool, v_pool)


def _stacked(k_pool: Any, v_pool: Any, layer) -> Tuple[Any, Any, jnp.ndarray]:
    """The pools as the kernels take them: stacked ``[L, nb, Hkv, bs, hd]``
    beside the layer as an int32 ``[1]`` scalar-prefetch operand.  One
    layer's pool (``layer`` None) is the one-layer stack: a reshape."""
    if layer is None:
        lift = lambda c: jax.tree.map(lambda a: a[None], c)
        return lift(k_pool), lift(v_pool), jnp.zeros((1,), jnp.int32)
    return k_pool, v_pool, jnp.asarray(layer, jnp.int32).reshape(1)


def _scale_rows(scale: jnp.ndarray, lay: jnp.ndarray) -> jnp.ndarray:
    """An int8 pool's scales ``[L, nb, Hkv, bs]`` as the kernel reads them,
    ``[1, nb, Hkv, 1, bs]`` of layer ``lay``: a (1, bs) block over a
    (1, bs) minor pair is legal on TPU, (1, bs) over (Hkv, bs) is not.
    That view is a relayout (a real copy) of what it covers, so it covers
    ONE layer's scales (1/32 of the layer's int8 bytes), which the index
    map then reaches at layer 0; the int8 values themselves are read
    where they lie."""
    one = jax.lax.dynamic_index_in_dim(scale, lay[0], 0, keepdims=True)
    return one[:, :, :, None, :]


# ------------------------------------------------ CP ring carry entry point


def _cp_kernel(
    tab_ref, off_ref, lay_ref, q_ref, *refs,
    S_in, bs, window, sm_scale, fetch_width, rows, nb, has_carry,
):
    """Ring-hop variant of :func:`_kernel` for context-parallel prefill
    (ops/ring_paged.py): the pool operand is ONE rank's slice
    [nb, Hkv, bs, hd] reached through a RE-BASED table (global id minus
    the source rank's slice base), so entries outside ``[0, nb)`` mean
    "another rank owns this block" — the index map clamps them onto a
    valid fetch and the in-kernel ownership test masks them out of the
    scores.  Instead of normalizing, the kernel RETURNS the raw online
    -softmax carry (acc, m, l); the ring accumulates it across hops
    (``has_carry`` seeds the scratch from the previous hop's output) and
    normalizes once after the last hop."""
    n_c = 3 if has_carry else 0
    carry_refs = refs[:n_c]
    kv_refs = refs[n_c:n_c + fetch_width * 2]
    acc_o, m_o, l_o = refs[n_c + fetch_width * 2:n_c + fetch_width * 2 + 3]
    acc_ref, m_ref, l_ref = refs[n_c + fetch_width * 2 + 3:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    off = off_ref[b]
    hi = (off + S_in + bs - 1) // bs  # live KV blocks for this slot

    @pl.when(j == 0)
    def _init():
        if has_carry:
            acc_ref[...] = carry_refs[0][0, 0]
            m_ref[...] = carry_refs[1][0, 0]
            l_ref[...] = carry_refs[2][0, 0]
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # [rows, hd]
    qpos = off + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) % S_in

    for i in range(fetch_width):
        blk = j * fetch_width + i

        @pl.when(blk < hi)
        def _compute(i=i, blk=blk):
            raw = tab_ref[b, blk]  # re-based id; out of [0, nb) = remote
            owned = (raw >= 0) & (raw < nb)
            kblk = kv_refs[2 * i][0, 0, 0]
            vblk = kv_refs[2 * i + 1][0, 0, 0]
            s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32)
            kpos = blk * bs + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bs), 1)
            keep = (kpos <= qpos) & owned
            if window is not None:
                keep = keep & (kpos > qpos - window)
            _accumulate(
                s * sm_scale, keep,
                lambda p: jnp.dot(p.astype(vblk.dtype), vblk,
                                  preferred_element_type=jnp.float32),
                acc_ref, m_ref, l_ref)

    @pl.when(j == (hi - 1) // fetch_width)
    def _write():
        acc_o[0, 0] = acc_ref[...]
        m_o[0, 0] = m_ref[...]
        l_o[0, 0] = l_ref[...]


def paged_carry_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables_local: jnp.ndarray,
    offsets,
    *,
    layer=None,
    carry: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    fetch_width: Optional[int] = None,
    q_pad_to: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One ring hop of CP paged prefill: accumulate ``q`` [B, H, S_in,
    hd] against layer ``layer`` of ONE rank's pool slice ``[L, nb, Hkv,
    bs, hd]`` (``layer=None``: one layer's ``[nb, Hkv, bs, hd]``, as in
    :func:`paged_decode_attention`) reached through
    ``tables_local`` (= global tables minus that rank's slice
    base; out-of-slice entries are masked in-kernel), returning the
    UN-normalized online-softmax carry ``(acc [B, Hkv, rows, hd] f32,
    m [B, Hkv, rows, 128] f32, l [B, Hkv, rows, 128] f32)``.

    ``offsets`` must already include the rank's sub-chunk base (the q
    rows sit at ``offsets[b] + arange(S_in)`` globally), so the existing
    live-length walk (``hi``), dead-step clamping and position masking
    carry over from :func:`paged_decode_attention` unchanged.  Pass the
    previous hop's return as ``carry`` to continue accumulation; finish
    with :func:`finalize_paged_carry`.  ``l`` may be zero mid-ring (no
    owned key seen yet) — only the final carry's ``l`` must be positive,
    guaranteed because each row's own position is pool-resident on
    exactly one rank.  Int8 pools are not supported (the engine rejects
    ``kv_quant`` under ``cp_axis``)."""
    if isinstance(k_pool, tuple):
        raise NotImplementedError(
            "paged_carry_attention does not support int8 pools")
    B, H, S_in, hd = q.shape
    k_pool, v_pool, lay = _stacked(k_pool, v_pool, layer)
    _L, nb, Hkv, bs, _hd = k_pool.shape
    groups, rem = divmod(H, Hkv)
    if rem:
        raise ValueError(
            f"GQA needs q heads divisible by kv heads, got {H} vs {Hkv}")
    mb = tables_local.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    offs = jnp.asarray(offsets, jnp.int32)
    if offs.ndim == 0:
        offs = jnp.broadcast_to(offs, (B,))
    R = groups * S_in
    fw, pad_to = _step_params(mb, fetch_width, q_pad_to)
    rows = -(-R // pad_to) * pad_to
    qr = q.reshape(B, Hkv, R, hd)
    if rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - R), (0, 0)))

    def qidx(b, h, j, tab, off, lay):
        return (b, h, 0, 0)

    def kvidx(b, h, j, tab, off, lay, i=0):
        # the decode kernel's fetch rule, plus a clamp of the re-based
        # table entry into the slice (remote blocks fetch SOME valid
        # block; the in-kernel ownership test masks the scores)
        blk, hg = fetched_block(tab, off, b, h, j, i, S_in=S_in, bs=bs, fw=fw)
        return (lay[0], jnp.clip(blk, 0, nb - 1), hg, 0, 0)

    has_carry = carry is not None
    in_specs = [pl.BlockSpec((1, 1, rows, hd), qidx)]
    operands = [qr]
    if has_carry:
        for c, lanes in zip(carry, (hd, _LANES, _LANES)):
            in_specs.append(pl.BlockSpec((1, 1, rows, lanes), qidx))
            operands.append(c)
    for i in range(fw):
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bs, hd), functools.partial(kvidx, i=i)))
        operands.append(k_pool)
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bs, hd), functools.partial(kvidx, i=i)))
        operands.append(v_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, -(-mb // fw)),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, rows, hd), qidx),
            pl.BlockSpec((1, 1, rows, _LANES), qidx),
            pl.BlockSpec((1, 1, rows, _LANES), qidx),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),      # acc
            pltpu.VMEM((rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((rows, _LANES), jnp.float32),  # l
        ],
    )
    kernel = functools.partial(
        _cp_kernel, S_in=S_in, bs=bs, window=window,
        sm_scale=float(sm_scale), fetch_width=fw, rows=rows, nb=nb,
        has_carry=has_carry)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _out_struct((B, Hkv, rows, hd), jnp.float32, q),
            _out_struct((B, Hkv, rows, _LANES), jnp.float32, q),
            _out_struct((B, Hkv, rows, _LANES), jnp.float32, q),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="paged_carry",
    )(tables_local.astype(jnp.int32), offs, lay, *operands)
    return acc, m, l


def finalize_paged_carry(carry, B: int, H: int, S_in: int, hd: int,
                         dtype) -> jnp.ndarray:
    """Normalize the last ring hop's carry and restore the public
    [B, H, S_in, hd] layout (undo group-major packing + row padding)."""
    acc, _m, l = carry
    Hkv = acc.shape[1]
    R = (H // Hkv) * S_in
    out = acc / l[..., :1]
    return out[:, :, :R].reshape(B, H, S_in, hd).astype(dtype)


# --------------------------------------------------- modeled HBM footprint


def modeled_attend_temp_bytes(
    impl: str, *, batch: int, kv_heads: int, max_blocks: int,
    block_size: int, head_dim: int, s_in: int = 1, groups: int = 1,
    itemsize: int = 4, fetch_width: Optional[int] = None,
    window: Optional[int] = None,
) -> int:
    """Modeled per-layer attention working-set bytes for one decode step —
    the MemoryModel-style no-compile estimate the 32k serving test (and a
    capacity planner) judges against ``obs.mem_ledger.headroom_verdict``.

    ``gather``: the dense per-slot view ``[B, Hkv, max_blocks*bs, hd]``
    materialized for k AND v (the int8 pool additionally upcasts both to
    f32 in the einsum, so ``itemsize=4`` models that case too) — O(max
    context) whatever the slot holds.  ``pallas``: what one program holds
    in VMEM (the q/out rows of its ``hb`` KV heads, the K and V blocks it
    keeps twice over: both halves of a key tile of ``T`` blocks where the
    shape takes the in-kernel walk, the ``fw`` double-buffered blocks of a
    grid step's tile where it walks the grid, and that tile's float32
    scores ``[hb x rows, keys]``; all from :func:`shape_walk`, as the
    wrapper asks) times the programs of one step: O(block), independent of
    context."""
    if impl == "gather":
        return 2 * batch * kv_heads * max_blocks * block_size * head_dim * itemsize
    if impl == "pallas":
        block = block_size * head_dim * itemsize
        split, _cols, rows, fw, hb, T = shape_walk(
            groups, s_in, kv_heads, max_blocks, block_size, block, window,
            fetch_width=fetch_width)
        tile = T or fw
        # one program: hb heads' q and out rows, the tile's K + V blocks
        # twice, its float32 scores
        program = hb * (2 * rows * head_dim * itemsize + 2 * 2 * tile * block
                        + 4 * rows * tile * block_size)
        return batch * (kv_heads * split // hb) * program
    raise ValueError(f"impl must be 'gather' or 'pallas', got {impl!r}")
