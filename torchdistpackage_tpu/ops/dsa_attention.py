"""Indexed (sparse) attention over the block pool (DeepSeek-V3.2-Exp's
"lightning indexer" in front of grouped-query attention; models/hybrid.py
kind ``S``): a query scores EVERY cached position with a second, small key,
keeps the ``topk`` best and attends to those alone.

Three computations, each a function of this module with two
implementations behind ``impl`` (``'pallas'``: the TPU's kernels, run by
the interpreter on a CPU; ``'gather'``: plain ``jax.numpy`` on a gathered
view, the parity oracle and the CPU's default):

1. :func:`index_scores` (kernel ``dsa_index``): ``I(t, s) = sum_j w[t, j]
   relu(qI[t, j] . kI[s])`` for every cached ``s <= t``, float32, the keys
   read through the block table from the pool's ``idx`` leaf ``[L, nb, 1,
   idx_dim, bs]`` (a block is the transpose of its rows, as the latent
   pool's: ``qI @ block`` needs no transpose and the minor dim is whole
   lanes).  Positions behind the query, and table columns past the slot's
   live blocks, read :data:`NEG_INF`.
2. :func:`select_bias` (kernel ``dsa_select``): per query the ``min(topk,
   t + 1)`` positions of largest score, equal scores the lower position
   first (``lax.top_k``'s rule), as an additive bias: 0 where selected,
   :data:`NEG_INF` elsewhere.  The kernel finds the ``topk``-th largest
   score of a row by bisection on the float's bit pattern (counting passes
   over a row tile that stays in VMEM: at most 32, and no more once exactly
   ``topk`` scores reach every row's threshold), and only where scores
   EQUAL to the threshold are more than may be kept, the position up to
   which they are taken (a second bisection): no sort, no indices, and
   exactly the set a sort would give.
3. :func:`selected_attention` (kernels ``dsa_decode`` / ``dsa_chunk``):
   grouped-query attention under that bias.  It WALKS every live block, as
   ``ops/paged_attention.py`` does, and masks the unselected positions: at
   this pool's layout a selected position is four 256-byte rows a side, and
   65,536 positions a layer as DMAs (or as an XLA gather) cost more than
   the whole walk at contexts of 4k-14k (PERF.md section 6, PR 39).  The
   decode shape walks in the kernel (one DMA a live block, a key tile a
   softmax step); a prefill chunk walks the grid as the paged kernel's
   chunk does (``ops.paged_attention.shape_walk``): a KV head's query rows
   of the WHOLE chunk are one program (past ``_PROGRAM_ROWS`` the fewest
   that come under it), so a head's blocks are fetched once a chunk, a
   grid step's blocks are one key tile of up to 1,024 keys and one softmax
   step, and the bias is the only mask: it reads :data:`NEG_INF` behind
   every query already, so the chunk kernel compares no positions.

Under ``impl='pallas'`` scores and bias lie BY BLOCK, ``[B, max_blocks, S_in,
bs]`` (column block, then query row, then the block's positions), from one
function of this module to the next: ``dsa_index`` writes a ``[rows, bs]``
tile a key block, ``dsa_select`` sweeps a row tile's live blocks as whole
vector registers (a count is register adds and ONE lane reduction a pass),
and the attention kernels read the tile of the key block they hold.  The
gathered oracle's lie naturally, ``[B, S_in, max_blocks * bs]``;
:func:`natural` and :func:`by_block` turn one into the other.  ``dsa_index``
names two kernels, one a call shape: a chunk's rows walk the grid, the decode
shape's one row a slot walks the slot's live blocks inside the kernel.

:func:`selection_words` packs a bias into bits, sixteen positions an int16:
what ``ServingEngine(record_routing=True)`` hands out of every call beside
the chosen experts, so that a reference in another precision can FOLLOW the
program's selection (a score on the other side of the ``topk``-th after
rounding is another key read, as a flipped expert is another function).

The kernels of ``ops/paged_attention.py`` are not touched: the two
attention kernels here are their bodies with one more operand, and their
walks are the ones that module's ``call_walk`` / ``shape_walk`` give a shape.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _out_struct
from .paged_attention import (
    _LANES,
    NEG_INF,
    _accumulate,
    _stacked,
    call_walk,
    shape_walk,
)

F32 = jnp.float32
#: query positions of one tile of ``dsa_index``'s chunk kernel
_Q_TILE = 128
#: index-key blocks a grid step of ``dsa_index`` fetches at most
_INDEX_FETCH = 16
#: rows of one ``dsa_select`` program (a float32 sublane tile)
_SELECT_ROWS = 8
#: VMEM ``dsa_chunk`` may take (the chip has 128 MiB).  Compiled alone for a
#: described v5e (PR 47) a program of 4,096 rows x a tile of 1,024 keys asks
#: for 36.37 MB: the float32 scores and probabilities, 8 bias blocks of
#: ``[512, 128]`` float32 twice over, q, out and the (acc, m, l) scratch
_CHUNK_VMEM_LIMIT = 64 << 20


def _canonical(s):
    """-0.0 -> +0.0: equal scores must be equal bit patterns."""
    return jnp.where(s == 0, 0.0, s)


def _offsets(offsets, B: int):
    offs = jnp.asarray(offsets, jnp.int32)
    return jnp.broadcast_to(offs, (B,)) if offs.ndim == 0 else offs


def by_block(a, bs: int):
    """[B, S, mb * bs] -> [B, mb, S, bs]."""
    B, S, P = a.shape
    return a.reshape(B, S, P // bs, bs).transpose(0, 2, 1, 3)


def natural(a):
    """[B, mb, S, bs] -> [B, S, mb * bs]."""
    B, mb, S, bs = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B, S, mb * bs)


def _tile(S_in: int) -> int:
    ts = min(S_in, _Q_TILE)
    if S_in % ts or (ts > 1 and ts % 8):
        raise ValueError(
            f"a call of {S_in} positions: one, or a multiple of 8 up to "
            f"{_Q_TILE}, or a multiple of {_Q_TILE}")
    return ts


# ------------------------------------------------------------ gather oracle


def _gathered(pool, tables, layer):
    """One layer's K or V blocks through the tables: [B, Hkv, mb * bs, hd]."""
    g = pool[tables] if layer is None else pool[layer, tables]
    B, mb, Hkv, bs, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, mb * bs, hd)


def _index_scores_gather(qi, w, idx_pool, tables, offs, layer):
    g = idx_pool[tables] if layer is None else idx_pool[layer, tables]
    B, mb, _one, W, bs = g.shape
    ki = g[:, :, 0].transpose(0, 1, 3, 2).reshape(B, mb * bs, W)
    # float32 operands: bf16 products are exact in float32, and the CPU has
    # no bf16 x bf16 -> f32 product with batch dims
    s = jnp.einsum("bjsd,bpd->bsjp", qi.astype(F32), ki.astype(F32),
                   precision=jax.lax.Precision.HIGHEST)
    s = _canonical(jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2))
    qpos = offs[:, None] + jnp.arange(qi.shape[2])[None, :]
    return jnp.where(jnp.arange(mb * bs)[None, None, :] <= qpos[..., None],
                     s, NEG_INF)


def _select_bias_gather(scores, topk: int):
    B, S, P = scores.shape
    _, idx = jax.lax.top_k(scores, min(topk, P))
    sel = jnp.zeros((B, S, P), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx
    ].set(True)
    return jnp.where(sel & (scores > 0.5 * NEG_INF), 0.0, NEG_INF).astype(F32)


def _selected_attention_gather(q, k_pool, v_pool, bias, tables, layer,
                               sm_scale):
    B, H, S_in, hd = q.shape
    kg, vg = (_gathered(p, tables, layer) for p in (k_pool, v_pool))
    Hkv = kg.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, S_in, hd)
    s = jnp.einsum("bkgqh,bkth->bkgqt", qg, kg).astype(F32) * sm_scale
    p = jax.nn.softmax(s + bias[:, None, None], axis=-1).astype(vg.dtype)
    return jnp.einsum("bkgqt,bkth->bkgqh", p, vg).reshape(B, H, S_in, hd)


# ---------------------------------------------------------------- dsa_index


def _live_columns(off, last_row, bs: int, mb: int):
    """Index of the last table column that rows up to ``last_row`` (call
    relative, inclusive bound + 1) of a slot at ``off`` can see."""
    return jnp.minimum((off + last_row + bs - 1) // bs, mb) - 1


def _held_column(hi1, j, i, fw: int):
    """The table column that sub-block operand ``i`` asks for at key step
    ``j`` (``ops.paged_attention.fetched_block``'s rule): its own while
    live, else the one it already holds, so that the pipeline fetches
    nothing; ``(column, live at all)``."""
    blk = j * fw + i
    own_last = i + fw * (jnp.maximum(hi1 - i, 0) // fw)
    return jnp.where(blk <= hi1, blk, own_last), i <= hi1


def _index_kernel(tab_ref, off_ref, lay_ref, q_ref, w_ref, *refs,
                  S_in, ts, bs, mb, fw, J):
    """Grid ``(slot b, query tile qt, key step j)``; ``q_ref`` [J * ts,
    idx_dim] head-major rows of the tile, ``w_ref`` [J * ts, 1] their
    weights, ``refs``: ``fw`` index-key blocks ``[idx_dim, bs]`` and the
    output ``[fw, ts, bs]``."""
    k_refs, o_ref = refs[:fw], refs[fw]
    b, qt, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    off = off_ref[b]
    hi1 = _live_columns(off, jnp.minimum((qt + 1) * ts, S_in), bs, mb)
    qpos = off + qt * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, bs), 0)
    for i in range(fw):
        blk = j * fw + i

        @pl.when(blk <= hi1)
        def _live(i=i, blk=blk):
            s = jnp.dot(q_ref[0, 0], k_refs[i][0, 0, 0],
                        preferred_element_type=F32)          # [J * ts, bs]
            r = jnp.maximum(s, 0.0) * w_ref[0, 0]
            if ts == 1:
                acc = jnp.sum(r, axis=0, keepdims=True)
            else:
                acc = r[:ts]
                for h in range(1, J):
                    acc = acc + r[h * ts:(h + 1) * ts]
            kpos = blk * bs + jax.lax.broadcasted_iota(
                jnp.int32, (ts, bs), 1)
            o_ref[0, i] = jnp.where(kpos <= qpos, _canonical(acc), NEG_INF)

        @pl.when(blk > hi1)
        def _dead(i=i):
            o_ref[0, i] = jnp.full((ts, bs), NEG_INF, F32)


@jax.jit
def _index_scores_pallas(qi, w, idx_pool, tables, offs, lay):
    """Scores BY BLOCK ``[B, mb, S_in, bs]``: the decode shape walks the
    slot's live blocks in the kernel, a chunk walks the grid."""
    B, J, S_in, di = qi.shape
    _L, _nb, _one, _di, bs = idx_pool.shape
    mb = tables.shape[-1]
    if S_in == 1:
        return _index_walk_call(qi, w, idx_pool, tables, offs, lay)
    ts = _tile(S_in)
    nqt = S_in // ts
    fw = max(d for d in range(1, min(_INDEX_FETCH, mb) + 1) if mb % d == 0)
    rows = qi.reshape(B, J, nqt, ts, di).transpose(0, 2, 1, 3, 4).reshape(
        B, nqt, J * ts, di)
    wr = w.astype(F32).reshape(B, nqt, ts, J).transpose(0, 1, 3, 2).reshape(
        B, nqt, J * ts, 1)

    def qidx(b, qt, j, tab, off, lay):
        return (b, qt, 0, 0)

    def kidx(b, qt, j, tab, off, lay, i=0):
        hi1 = _live_columns(off[b], jnp.minimum((qt + 1) * ts, S_in), bs, mb)
        col, live = _held_column(hi1, j, i, fw)
        return (lay[0], jnp.where(live, tab[b, jnp.minimum(col, mb - 1)], 0),
                0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nqt, mb // fw),
        in_specs=[pl.BlockSpec((1, 1, J * ts, di), qidx),
                  pl.BlockSpec((1, 1, J * ts, 1), qidx)] + [
            pl.BlockSpec((1, 1, 1, di, bs), functools.partial(kidx, i=i))
            for i in range(fw)],
        out_specs=pl.BlockSpec(
            (1, fw, ts, bs), lambda b, qt, j, tab, off, lay: (b, j, qt, 0)),
    )
    kernel = functools.partial(_index_kernel, S_in=S_in, ts=ts, bs=bs, mb=mb,
                               fw=fw, J=J)
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((B, mb, S_in, bs), F32, qi),
        compiler_params=params, interpret=_interpret(), name="dsa_index",
    )(tables.astype(jnp.int32), offs, lay, rows, wr, *([idx_pool] * fw))


#: index-key blocks one tile of the decode shape's walk holds (16 KB each
#: at the published sizes)
_INDEX_TILE = 16


def _index_walk_kernel(tab_ref, off_ref, lay_ref, q_ref, w_ref, k_hbm, o_ref,
                       kbuf, sem, *, bs, mb, T):
    """The decode shape: grid ``(slot b,)``, the ``idx`` leaf left in HBM,
    and a loop over the slot's LIVE blocks in tiles of ``T``, tile ``t + 1``
    copied into one half of ``kbuf`` [2, T, idx_dim, bs] while tile ``t`` is
    scored from the other: one DMA a live block, nothing fetched or computed
    for a dead table column (its scores stay :data:`NEG_INF`)."""
    b = pl.program_id(0)
    lay, off = lay_ref[0], off_ref[b]
    live = jnp.minimum((off + bs) // bs, mb)
    tiles = (live + T - 1) // T

    def copies(t, half, act):
        def block(i, carry):
            act(pltpu.make_async_copy(
                k_hbm.at[lay, tab_ref[b, t * T + i], 0], kbuf.at[half, i],
                sem.at[half]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(live - t * T, 0, T), block, None)

    copies(0, 0, lambda c: c.start())
    o_ref[...] = jnp.full(o_ref.shape, NEG_INF, F32)
    q, w = q_ref[0], w_ref[0]                   # [J, idx_dim], [J, 1]

    def tile(t, carry):
        half = t % 2

        @pl.when(t + 1 < tiles)
        def _next():
            copies(t + 1, 1 - half, lambda c: c.start())

        copies(t, half, lambda c: c.wait())

        def block(i, carry):
            s = jnp.dot(q, kbuf[half, i], preferred_element_type=F32)
            acc = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
            kpos = (t * T + i) * bs + jax.lax.broadcasted_iota(
                jnp.int32, (1, bs), 1)
            o_ref[0, t * T + i] = jnp.where(kpos <= off, _canonical(acc),
                                            NEG_INF)
            return carry

        jax.lax.fori_loop(0, jnp.clip(live - t * T, 0, T), block, None)
        return carry

    jax.lax.fori_loop(0, tiles, tile, None)


def _index_walk_call(qi, w, idx_pool, tables, offs, lay):
    B, J, _one, di = qi.shape
    bs = idx_pool.shape[-1]
    mb = tables.shape[-1]
    T = min(_INDEX_TILE, mb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, J, di), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec((1, J, 1), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, mb, 1, bs), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, T, di, bs), idx_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    kernel = functools.partial(_index_walk_kernel, bs=bs, mb=mb, T=T)
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((B, mb, 1, bs), F32, qi),
        compiler_params=params, interpret=_interpret(), name="dsa_index",
    )(tables.astype(jnp.int32), offs, lay, qi[:, :, 0],
      w.astype(F32).reshape(B, J, 1), idx_pool)


def index_scores(qi, w, idx_pool, tables, offsets, *, layer=None,
                 impl: str = "gather"):
    """``qi`` [B, J, S_in, idx_dim], ``w`` [B, S_in, J] float32, the pool's
    ``idx`` leaf ``[L, nb, 1, idx_dim, bs]`` (``layer`` None: one layer's
    ``[nb, 1, idx_dim, bs]``) -> scores float32, [B, S_in, max_blocks * bs]
    (``'pallas'``: by block, [B, max_blocks, S_in, bs]): slot b's row r is
    the query at position ``offsets[b] + r``, column p the cached position p
    of that slot; :data:`NEG_INF` behind the query."""
    offs = _offsets(offsets, qi.shape[0])
    if impl != "pallas":
        return _index_scores_gather(qi, w, idx_pool, tables, offs, layer)
    if layer is None:   # one layer's leaf is the one-layer stack
        idx_pool, layer = idx_pool[None], 0
    return _index_scores_pallas(qi, w, idx_pool, tables, offs,
                                jnp.asarray(layer, jnp.int32).reshape(1))


# --------------------------------------------------------------- dsa_select


#: key blocks one step of a counting pass takes (the scratch is padded to a
#: multiple of it)
_SELECT_GROUP = 8
#: the order-preserving integer key (:func:`_select_kernel`) of NEG_INF / 2:
#: a key above it is a real score's
_REAL_KEY = int(np.array(0.5 * NEG_INF, np.float32).view(np.int32)) ^ 0x7FFFFFFF


def _select_kernel(act_ref, live_ref, s_ref, o_ref, key_ref, *,
                   topk, mb, bs, nbits):
    """One tile of ``_SELECT_ROWS`` rows of one group: ``s_ref`` [mb, rows,
    bs] scores by block.  ``act_ref[g, i]`` 0: no row of the tile has more
    than ``topk`` positions to choose from, and every real score is kept.
    ``live_ref[g, i]``: how many groups of :data:`_SELECT_GROUP` blocks hold
    the tile's causal positions; a counting pass sweeps those alone."""
    g, i = pl.program_id(0), pl.program_id(1)
    rows, grp = s_ref.shape[2], _SELECT_GROUP
    int_min = jnp.int32(-2 ** 31)

    @pl.when(act_ref[g, i] == 0)
    def _every():
        o_ref[0] = jnp.where(s_ref[0] > 0.5 * NEG_INF, 0.0, NEG_INF)

    @pl.when(act_ref[g, i] != 0)
    def _choose():
        u = jax.lax.bitcast_convert_type(s_ref[0], jnp.int32)
        # float order as signed-integer order: a negative float's magnitude
        # bits are flipped
        key_ref[:mb] = u ^ (jnp.right_shift(u, 31) & jnp.int32(0x7FFFFFFF))
        if key_ref.shape[0] > mb:
            key_ref[mb:] = jnp.full(
                (key_ref.shape[0] - mb, rows, bs), int_min, jnp.int32)
        groups = live_ref[g, i]

        def count(hit):
            """Rows' counts [rows, 1] of ``hit(keys [grp, rows, bs],
            positions)`` over the live groups."""
            def step(c, acc):
                keys = key_ref[pl.ds(c * grp, grp)]
                pos = (c * grp + jax.lax.broadcasted_iota(
                    jnp.int32, keys.shape, 0)) * bs + (
                    jax.lax.broadcasted_iota(jnp.int32, keys.shape, 2))
                return acc + jnp.sum(
                    jnp.where(hit(keys, pos), 1.0, 0.0), axis=0)

            acc = jax.lax.fori_loop(0, groups, step, jnp.zeros((rows, bs), F32))
            return jnp.sum(acc, axis=-1, keepdims=True)

        def value_bit(state):
            # ``t``: the threshold so far, offset binary (unsigned order in
            # int32 bits); keep the bit if ``topk`` keys still reach it.
            # ``reach``: how many keys reach ``t``
            n, t, reach = state
            cand = t | jnp.left_shift(jnp.int32(1), 31 - n)
            got = count(lambda k, _: k >= (cand ^ int_min))
            enough = got >= topk
            return (n + 1, jnp.where(enough, cand, t),
                    jnp.where(enough, got, reach))

        def undecided(state):
            # once exactly ``topk`` keys reach every row's threshold, the
            # bits left would only move it through a gap that holds no key
            n, _t, reach = state
            return (n < 32) & (jnp.max(jnp.abs(reach - topk)) > 0)

        _n, t, reach = jax.lax.while_loop(
            undecided, value_bit,
            (jnp.int32(0), jnp.zeros((rows, 1), jnp.int32),
             jnp.full((rows, 1), 2.0 * mb * bs, F32)))
        t = t ^ int_min

        def among_equals():
            """Some row's threshold is shared by more scores than it may
            keep (or it has fewer than ``topk`` real ones): the position up
            to which the equal scores are taken, by a second bisection."""
            need = topk - count(lambda k, _: k > t)

            def position_bit(n, last):
                # ``last``: the largest position bound below which fewer
                # than ``need`` of the scores equal to the threshold lie
                cand = last | jnp.left_shift(jnp.int32(1), nbits - 1 - n)
                short = count(lambda k, pos: (k == t) & (pos < cand)) < need
                return jnp.where(short, cand, last)

            return jax.lax.fori_loop(0, nbits, position_bit,
                                     jnp.zeros((rows, 1), jnp.int32))

        last = jax.lax.cond(
            jnp.max(jnp.abs(reach - topk)) > 0, among_equals,
            lambda: jnp.full((rows, 1), mb * bs, jnp.int32))

        def write(c, carry):
            k = key_ref[c]
            pos = c * bs + jax.lax.broadcasted_iota(jnp.int32, k.shape, 1)
            keep = ((k > t) | ((k == t) & (pos <= last))) & (k > _REAL_KEY)
            o_ref[0, c] = jnp.where(keep, 0.0, NEG_INF)
            return carry

        jax.lax.fori_loop(0, mb, write, None)


@functools.partial(jax.jit, static_argnames=("topk",))
def _select_bias_pallas(scores, offs, *, topk: int):
    """Scores by block ``[B, mb, S_in, bs]`` -> the bias by block.  The
    decode shape (one row a slot) is turned so that SLOTS are a tile's
    rows; a chunk's rows are its positions."""
    B, mb, S_in, bs = scores.shape
    qpos = offs[:, None] + jnp.arange(S_in)[None, :]           # [B, S_in]
    if S_in == 1:
        scores, qpos = scores.transpose(2, 1, 0, 3), qpos.T   # one group
    G, _mb, R, _bs = scores.shape
    tr, grp = _SELECT_ROWS, _SELECT_GROUP
    pad = -R % tr
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, pad), (0, 0)),
                         constant_values=NEG_INF)
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)))
    tiles = qpos.reshape(G, -1, tr)
    active = jnp.any(tiles >= topk, axis=-1).astype(jnp.int32)
    mbp = -(-mb // grp) * grp
    live = jnp.minimum(-(-(jnp.max(tiles, axis=-1) + 1) // (bs * grp)),
                       mbp // grp).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(G, (R + pad) // tr),
        in_specs=[pl.BlockSpec((1, mb, tr, bs),
                               lambda g, i, act, live: (g, 0, i, 0))],
        out_specs=pl.BlockSpec((1, mb, tr, bs),
                               lambda g, i, act, live: (g, 0, i, 0)),
        scratch_shapes=[pltpu.VMEM((mbp, tr, bs), jnp.int32)],
    )
    kernel = functools.partial(
        _select_kernel, topk=topk, mb=mb, bs=bs,
        nbits=max(1, (mb * bs - 1).bit_length()))
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct(scores.shape, F32, scores),
        compiler_params=params, interpret=_interpret(), name="dsa_select",
    )(active, live, scores)[:, :, :R]
    return out.transpose(2, 1, 0, 3) if S_in == 1 else out


def select_bias(scores, offsets, topk: int, *, impl: str = "gather"):
    """:func:`index_scores`' result -> the selection as an additive bias of
    the same shape, float32: 0 at the ``min(topk, t + 1)`` positions of
    largest score of each query (equal scores: the lower position first),
    :data:`NEG_INF` at every other."""
    if impl == "pallas":
        return _select_bias_pallas(
            scores, _offsets(offsets, scores.shape[0]), topk=int(topk))
    return _select_bias_gather(scores, int(topk))


def selection_words(bias):
    """A bias (either layout) as bits, [B, S_in, ceil(positions / 16)]
    int16: bit i of a row's word j says that position ``16 j + i`` is kept.
    Sixteen positions are one product with the powers of two (exact: the
    sum stays under 2^16), 128 at a time where a block is that wide, so a
    block's lanes are never split."""
    if bias.ndim == 4 and bias.shape[-1] % 16:
        bias = natural(bias)
    P = bias.shape[-1]
    g = 128 if P % 128 == 0 else 16
    keep = jnp.pad(bias > 0.5 * NEG_INF,
                   [(0, 0)] * (bias.ndim - 1) + [(0, -P % g)])
    pack = np.zeros((g, g // 16), np.float32)
    pack[np.arange(g), np.arange(g) // 16] = 2.0 ** (np.arange(g) % 16)
    words = jnp.dot(keep.reshape(*keep.shape[:-1], -1, g).astype(F32), pack,
                    preferred_element_type=F32)
    words = jax.lax.bitcast_convert_type(
        words.reshape(*keep.shape[:-1], -1).astype(jnp.uint16), jnp.int16)
    return natural(words) if words.ndim == 4 else words


# ------------------------------------------------- dsa_decode and dsa_chunk


def _decode_kernel(
    tab_ref, off_ref, lay_ref, q_ref, bias_ref, k_hbm, v_hbm, o_ref,
    kbuf, vbuf, sem, par_ref, acc_ref, m_ref, l_ref,
    *, bs, mb, sm_scale, rows, hb, T,
):
    """``ops.paged_attention._walk_kernel`` for one query position a slot,
    with the selection: ``bias_ref`` [tiles * T, 1, bs] is the slot's bias by
    block, a key tile's ``T`` laid side by side and added to the scaled
    scores of every query row (the selection is one for all heads).  A key tile none of whose positions is selected
    leaves ``m`` at :data:`NEG_INF` and its probabilities at 1; the first
    tile with a selected position rescales that away (``exp(NEG_INF - m)``
    is 0), and every query has one."""
    b, h = pl.program_id(0), pl.program_id(1)
    nh = pl.num_programs(1)
    lay = lay_ref[0]

    def live_blocks(b):
        return jnp.minimum((off_ref[b] + 1 + bs - 1) // bs, mb)

    def tile_copies(b, h, t, half, act):
        def block(i, carry):
            src = (lay, tab_ref[b, t * T + i], pl.ds(h * hb, hb))
            dst = (half, slice(None), pl.ds(pl.multiple_of(i * bs, bs), bs))
            for pool, buf, side in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                act(pltpu.make_async_copy(
                    pool.at[src], buf.at[dst], sem.at[half, side]))
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
    last = off + 1
    tiles = (live_blocks(b) + T - 1) // T
    par0 = par_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]  # [hb, rows, hd]

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
        k = kbuf[half]  # [hb, T*bs, hd]
        v = vbuf[half]
        s = jnp.einsum("hrd,hkd->hrk", q, k, preferred_element_type=F32)
        kpos = t * (T * bs) + jax.lax.broadcasted_iota(
            jnp.int32, (hb, rows, T * bs), 2)
        written = t * (T * bs) + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 1) < last
        v = jnp.where(written, v, 0)
        bias = jnp.concatenate(
            [bias_ref[0, t * T + i] for i in range(T)], axis=-1)
        _accumulate(
            s * sm_scale + bias, kpos <= off,
            lambda p: jnp.einsum("hrk,hkd->hrd", p.astype(v.dtype), v,
                                 preferred_element_type=F32),
            acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, tiles, tile, None)
    par_ref[0] = (par0 + tiles) % 2
    o_ref[0] = (acc_ref[...] / l_ref[..., :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def _decode_attention_pallas(q, k_pool, v_pool, bias, tables, offs, lay, *,
                             sm_scale: float):
    B, H, _one, hd = q.shape
    _L, _nb, Hkv, bs, _hd = k_pool.shape
    mb = tables.shape[-1]
    G = H // Hkv
    rows, _fw, hb, T = call_walk(
        G, Hkv, mb, bs, bs * hd * k_pool.dtype.itemsize)
    if not T:
        raise ValueError(f"{G} query rows a KV head do not fit one tile")
    qr = q.reshape(B, Hkv, G, hd)
    if rows != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - G), (0, 0)))
    nt = -(-mb // T)
    bt = jnp.pad(bias, ((0, 0), (0, nt * T - mb), (0, 0), (0, 0)),
                 constant_values=NEG_INF)

    def qidx(b, h, tab, off, lay):
        return (b, h, 0, 0)

    tile = pltpu.VMEM((2, hb, T * bs, hd), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv // hb),
        in_specs=[pl.BlockSpec((1, hb, rows, hd), qidx),
                  pl.BlockSpec((1, nt * T, 1, bs),
                               lambda b, h, tab, off, lay: (b, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hb, rows, hd), qidx),
        scratch_shapes=[
            tile, tile,                                   # K, V tiles x 2
            pltpu.SemaphoreType.DMA((2, 2)),              # [half, K | V]
            pltpu.SMEM((1,), jnp.int32),                  # first tile's half
            pltpu.VMEM((hb, rows, hd), F32),              # acc
            pltpu.VMEM((hb, rows, _LANES), F32),          # m
            pltpu.VMEM((hb, rows, _LANES), F32),          # l
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, mb=mb,
                               sm_scale=sm_scale, rows=rows, hb=hb, T=T)
    # programs run in order: each starts the next one's first copies
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((B, Hkv, rows, hd), q.dtype, q),
        compiler_params=params, interpret=_interpret(), name="dsa_decode",
    )(tables.astype(jnp.int32), offs, lay, qr, bt, k_pool, v_pool)
    return out[:, :, :G].reshape(B, H, 1, hd)


def _accumulate_biased(s, pv, acc_ref, m_ref, l_ref):
    """``ops.paged_attention._accumulate`` for scores whose bias is their
    only mask: one key tile's online-softmax step on the (acc, m, l)
    scratch, ``s`` the scaled, biased f32 scores ``[..., S_in, keys]``,
    ``pv(p)`` the f32 ``[..., S_in, hd]`` product of the tile's
    probabilities with its values.  No ``where`` over the scores."""
    m = m_ref[..., :1]
    l = l_ref[..., :1]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_ref[...] = jnp.broadcast_to(
        l * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + pv(p)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _chunk_kernel(tab_ref, off_ref, lay_ref, q_ref, *refs,
                  S_in, bs, mb, fw, sm_scale):
    """Grid ``(slot b, program h, key step j)``: the grid's walk of
    ``ops.paged_attention._kernel``.  A program is ``hb`` KV heads' query
    rows of the WHOLE chunk (``q_ref`` [1, hb, rows, hd], rows group-major:
    row ``g * S_in + s`` is group g's query at the chunk's position s; past
    ``_PROGRAM_ROWS`` one of the ``split`` shares of one head's groups), so
    a head's K and V blocks are fetched once a chunk.  A step's ``fw``
    blocks side by side are ONE key tile and one online-softmax step.  The
    selection's bias ``[S_in, fw * bs]`` of the tile, the same for every
    group, is the ONLY mask: it reads :data:`NEG_INF` at every position that
    is unselected, behind its query, or in a column past the slot's live
    blocks, so no position is compared with any other.  A dead sub-block of
    a live tile (its column past the slot's last live one, or past the
    table's last) holds K, V and bias blocks fetched earlier
    (:func:`_held_column`: the pipeline fetches nothing); ONE scalar test a
    sub-block puts a block's worth of :data:`NEG_INF` in place of the bias
    it holds.  A tile none of whose positions a row selected leaves that
    row's ``m`` at :data:`NEG_INF` and its probabilities at 1; the next tile
    with a selected position rescales that away (``exp(NEG_INF - m)`` is 0),
    and every query keeps a position at or before its own.
    ``refs``: ``fw`` x (K block [1, 1, hb, bs, hd], V block, bias block
    [1, 1, S_in, bs]), the output, the (acc, m, l) scratch ``[hb x groups,
    S_in, ...]``."""
    kv_refs, o_ref = refs[:3 * fw], refs[3 * fw]
    acc_ref, m_ref, l_ref = refs[3 * fw + 1:]
    b, j = pl.program_id(0), pl.program_id(2)
    hi1 = _live_columns(off_ref[b], S_in, bs, mb)
    K = fw * bs

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * fw <= hi1)
    def _compute():
        side = lambda n: jnp.concatenate(
            [kv_refs[3 * i + n][0, 0] for i in range(fw)], axis=1)
        k, v = side(0), side(1)                      # [hb, K, hd]
        bias = jnp.concatenate(
            [jnp.where(j * fw + i <= hi1, kv_refs[3 * i + 2][0, 0], NEG_INF)
             for i in range(fw)], axis=1)            # [S_in, K]
        q = q_ref[0]                                 # [hb, rows, hd]
        hb, rows, _hd = q.shape
        s = jnp.einsum("hrd,hkd->hrk", q, k, preferred_element_type=F32)
        _accumulate_biased(
            s.reshape(-1, S_in, K) * sm_scale + bias,
            lambda p: jnp.einsum(
                "hrk,hkd->hrd", p.astype(v.dtype).reshape(hb, rows, K), v,
                preferred_element_type=F32).reshape(acc_ref.shape),
            acc_ref, m_ref, l_ref)

    @pl.when(j == hi1 // fw)
    def _write():
        o_ref[0] = (acc_ref[...] / l_ref[..., :1]).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale",))
def _chunk_attention_pallas(q, k_pool, v_pool, bias, tables, offs, lay, *,
                            sm_scale: float):
    """The walk follows from the call's shape alone, as the paged wrapper's
    (``ops.paged_attention.shape_walk``: what ``ServingEngine`` writes on its
    ``tdp:engine.init.pool`` span as ``chunk_rows`` / ``chunk_tile_keys`` /
    ``chunk_programs``): the programs a KV head's rows are dealt to, the KV
    heads of one program and the blocks of one key tile."""
    B, H, S_in, hd = q.shape
    _L, _nb, Hkv, bs, _hd = k_pool.shape
    mb = tables.shape[-1]
    G = H // Hkv
    split, cols, rows, fw, hb, T = shape_walk(
        G, S_in, Hkv, mb, bs, bs * hd * k_pool.dtype.itemsize)
    fw = T or fw    # a few rows a head: the decode walk's tile, on the grid
    if rows != G * S_in // split:
        raise ValueError(
            f"a chunk of {S_in} positions: a program's {G * S_in // split} "
            f"query rows would be padded to {rows}; a multiple of 8")
    progs = Hkv * split // hb

    def column(b, j, off, i):
        col, live = _held_column(
            _live_columns(off[b], S_in, bs, mb), j, i, fw)
        return jnp.minimum(col, mb - 1), live

    def qidx(b, h, j, tab, off, lay):
        return (b, h, 0, 0)

    def kvidx(b, h, j, tab, off, lay, i=0):
        col, live = column(b, j, off, i)
        return (lay[0], jnp.where(live, tab[b, col], 0),
                jnp.where(live, h // split, 0), 0, 0)

    def bidx(b, h, j, tab, off, lay, i=0):
        col, live = column(b, j, off, i)
        return (b, jnp.where(live, col, 0), 0, 0)

    in_specs = [pl.BlockSpec((1, hb, rows, hd), qidx)]
    operands = [q.reshape(B, Hkv * split, rows, hd)]
    for i in range(fw):
        for pool in (k_pool, v_pool):
            in_specs.append(pl.BlockSpec(
                (1, 1, hb, bs, hd), functools.partial(kvidx, i=i)))
            operands.append(pool)
        in_specs.append(pl.BlockSpec((1, 1, S_in, bs),
                                     functools.partial(bidx, i=i)))
        operands.append(bias)
    groups = hb * rows // S_in
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, progs, -(-cols // fw)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, rows, hd), qidx),
        scratch_shapes=[
            pltpu.VMEM((groups, S_in, hd), F32),       # acc
            pltpu.VMEM((groups, S_in, _LANES), F32),   # m
            pltpu.VMEM((groups, S_in, _LANES), F32),   # l
        ],
    )
    kernel = functools.partial(_chunk_kernel, S_in=S_in, bs=bs, mb=mb, fw=fw,
                               sm_scale=sm_scale)
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_CHUNK_VMEM_LIMIT)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((B, Hkv * split, rows, hd), q.dtype, q),
        compiler_params=params, interpret=_interpret(), name="dsa_chunk",
    )(tables.astype(jnp.int32), offs, lay, *operands)
    return out.reshape(B, H, S_in, hd)


def selected_attention(q, k_pool, v_pool, bias, tables, offsets, *,
                       layer=None, sm_scale: Optional[float] = None,
                       impl: str = "gather"):
    """Grouped-query attention of ``q`` [B, H, S_in, hd] against each slot's
    paged context in layer ``layer`` of the pools ``[L, nb, Hkv, bs, hd]``
    (None: one layer's), restricted to the positions ``bias`` selects
    (:func:`select_bias`: a subset of the causal ones, the same for every
    head; ``'pallas'``: by the pool's blocks).  Returns [B, H, S_in, hd]."""
    B, H, S_in, hd = q.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd))
    if impl != "pallas":
        return _selected_attention_gather(q, k_pool, v_pool, bias, tables,
                                          layer, scale)
    k_pool, v_pool, lay = _stacked(k_pool, v_pool, layer)
    fn = _decode_attention_pallas if S_in == 1 else _chunk_attention_pallas
    return fn(q, k_pool, v_pool, bias, tables, _offsets(offsets, B), lay,
              sm_scale=scale)


def indexed_attention(q, k_pool, v_pool, idx_pool, qi, w, tables, offsets, *,
                      topk: int, layer=None, impl: str = "gather"):
    """The three steps in a row, what an ``S`` layer's ``attend`` is:
    ``(attention's output, the selection as :func:`selection_words`)``."""
    scores = index_scores(qi, w, idx_pool, tables, offsets, layer=layer,
                          impl=impl)
    bias = select_bias(scores, offsets, topk, impl=impl)
    out = selected_attention(q, k_pool, v_pool, bias, tables, offsets,
                             layer=layer, impl=impl)
    return out, selection_words(bias)


# --------------------------------------------------------------- host counts


def position_counts(offsets, n_valid, topk: int) -> tuple:
    """``(indexed, selected)`` of one call, from its rows' offsets and real
    positions (host arithmetic, numpy): the (query, cached position) pairs
    the indexer scores, ``sum over real rows of t + 1``, and the pairs
    attention then reads, ``sum of min(topk, t + 1)``."""
    o = np.asarray(offsets, np.int64)
    n = np.asarray(n_valid, np.int64)
    indexed = int((n * o + n * (n + 1) // 2).sum())
    # rows whose context is still within topk select all of it
    under = np.clip(topk - o, 0, n)
    selected = int((under * o + under * (under + 1) // 2
                    + (n - under) * topk).sum())
    return indexed, selected
