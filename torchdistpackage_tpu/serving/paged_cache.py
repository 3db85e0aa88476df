"""Paged KV cache: a block-pool layout for the serving engine.

``models/generate.py`` reserves one contiguous ``[B, Hkv, max_len, hd]``
strip per sequence — every request pays ``max_len`` KV positions of HBM up
front, whatever it actually uses, and a batch must share one prompt length
and one decode budget.  The vLLM observation is that a KV cache is a heap,
not an array: carve the buffer into fixed ``block_size``-position blocks,
hand each sequence an int32 *block table* naming the blocks it owns, and
both problems disappear — memory is allocated in block quanta as the
sequence grows, and sequences of wildly different lengths coexist in one
fixed-shape decode batch.

TPU-first translation (everything here is static-shape, so the decode step
compiles ONCE):

- **Pool**: ``{'k','v': [L, num_blocks, Hkv, block_size, hd]}`` — one
  device buffer for the whole engine.  ``quantized=True`` stores int8
  ``(q8, scale)`` pairs via the same ``_kv_quant`` per-vector symmetric
  scheme as the contiguous cache (scale ``[L, num_blocks, Hkv,
  block_size]`` f32), halving KV HBM at long context.
  A latent-attention model (``models/hybrid.py``, kind ``L``) caches one
  row a position, shared by all heads, and its pool is ONE leaf,
  ``{'kv': [L, num_blocks, 1, W, block_size]}``: no per-head axis to speak
  of, no separate V, each block the transpose of its rows
  (ops/mla_attention.py says why).  A model whose attention is INDEXED
  (kind ``S``) keeps K and V as every GQA model does and, beside them, the
  indexer's one small key a position in a THIRD leaf, ``{'k', 'v', 'idx':
  [L, num_blocks, 1, idx_dim, block_size]}``, laid as the latent pool's
  blocks are and written by the same op.  Everything below that names blocks
  (tables, allocator, copy-on-write, migration) is the same for all three.
  A model with WINDOW layers beside global ones (kind ``W``) keeps two
  different amounts of cache and so has a SECOND pool (of the same block
  shape, or of its own: "Unequal widths, two block shapes" below) under
  ``'win'``, ``{'k','v': [window_layers, window_blocks, Hkv,
  block_size, hd]}``, with block ids, a NULL block, an allocator and a
  table of its own: a sequence holds at most :func:`window_reach` of its
  blocks, and the engine hands a block that fell behind the window on to a
  column ahead (docs/serving.md "Two pools").
  **Narrow heads.**  A TPU holds a bfloat16 array in tiles of 128 lanes
  along its minor dimension: a pool of 64-wide heads would be held at twice
  its bytes, the paged kernel cannot slice it (Mosaic: "slice shape must be
  aligned to tiling (128), but is 64") and XLA copies it whole into a padded
  form for each call that reads it.  A model whose heads fill a lane row
  exactly in twos or fours (``cfg.kv_pack``, models/hybrid.py) gets the pool
  ``[L, num_blocks, Hkv / pack, block_size, pack * hd]``: ``pack`` KV heads
  of one position side by side in a row (:func:`pack_heads`).  The write
  lays a call's rows out that way; the attention ops, kernel and gathered
  oracle alike, are handed a query whose head lies in the lanes of ITS KV
  head with zeros in the others (the score is then the head's own, term for
  term), an explicit ``sm_scale``, and give back a ``pack * hd`` wide row of
  which the head's lanes are kept (:func:`paged_attention`).  A pool of
  ``Hkv / pack`` heads of 128: the kernel, the tables and the allocator see
  nothing else.
  **Unequal widths, two block shapes.**  A model may state value heads
  narrower than its key heads and another number of KV heads in its window
  layers (``cfg.v_head_dim``, ``cfg.window_kv_heads``, models/hybrid.py:
  MiMo-V2 has 64 query heads of 192 over values of 128, 4 KV heads in the
  global layers and 8 in the window layers).  The two pools then have two
  block shapes, each taken from its own head count, and K and V are leaves
  of unequal width in both.  A 192-wide key is a lane tile and a half: a
  ``[.., block_size, 192]`` bfloat16 leaf would be held at 256 lanes, a
  third of it padding (and a 64-wide remainder is what Mosaic refused to
  slice, above).  So wherever the widths differ the K leaf lies
  TRANSPOSED, ``[L, num_blocks, Hkv, key_width, block_size]``, as the
  latent and the indexer leaves do: the positions are the lanes, whole
  tiles at a block of 128, the 192 dims are sublanes (twelve bfloat16
  tiles of 16), and the leaf holds its logical bytes.  A score is then
  ``q [rows, 192] . k^T [192, keys]``, the product as the MXU takes it,
  with no transpose of the key tile.  (Two leaves, the rotated 64 dims
  packed two heads to a row beside the other 128, hold the same bytes but
  make every score two products and every write two scatters.)  The V
  leaf lies as ever, ``[.., Hkv, block_size, value_width]``.  Equal widths:
  the layout above, leaf for leaf.
- **Block tables**: ``[num_slots, max_blocks]`` int32 per-slot rows.  Block
  ``i`` of a slot's table covers its positions ``[i*bs, (i+1)*bs)``, so the
  table IS the page table and position arithmetic is two integer ops.
  Block 0 is the engine's NULL block (never allocated): inactive slots and
  out-of-range clamped writes land there and are never read.
- **Write** is a vectorized scatter (disjoint blocks per slot — no
  collisions among live slots); **attend** has two implementations behind
  ``attn_impl`` (docs/serving.md "Paged attention kernel"): ``'gather'``
  gathers a slot's blocks into a dense ``[B, Hkv, max_blocks*bs, hd]``
  view through the table and runs the SAME ``_cached_attention`` as the
  contiguous path with per-slot [B] offsets — gathered index ==
  slot-relative position (tables list blocks in order), so the
  causal/sliding-window mask carries over unchanged, and when the
  gathered view matches the contiguous buffer's length the two paths
  agree BITWISE (tests/test_serving.py locks this for dense, GQA,
  sliding-window, and MoE families); ``'pallas'``
  (ops/paged_attention.py, the TPU default) walks the table INSIDE a
  fused kernel — same semantics, no gathered view, per-tick HBM bounded
  by live context (tests/test_paged_attention.py locks engine-token bit
  parity against the gather goldens).  The gather path stays as the
  parity oracle.

The allocator (:class:`BlockAllocator`) is host-side and O(blocks): the
hot loop never reallocates device memory — host code only rewrites small
int32 tables between compiled steps (see ``serving/engine.py``).
"""

from __future__ import annotations

import functools
import math
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.lax import axis_size as _axis_size
from ..models.generate import (
    _cached_attention,
    _embed_at,
    _kv_quant,
    cached_block_forward,
)
from ..models.gpt import GPTConfig, gpt_head
from ..parallel.tensor_parallel.layers import rope_cache
from ..utils import profiling as prof

PyTree = Any

#: Block id 0 is reserved by the engine as the write-off target: inactive
#: slots' tables are all-zero and clamped out-of-range writes land here.
#: No live slot's table ever references it, so its contents are never read.
NULL_BLOCK = 0


def init_paged_kv(
    cfg: GPTConfig, num_blocks: int, block_size: int, axis_size: int = 1,
    quantized: bool = False, window_blocks: int = 0,
) -> Dict[str, Any]:
    """Zeroed block pool ``{'k','v': [L, num_blocks, Hkv_local, block_size,
    hd]}`` in ``cfg.dtype`` — the paged analogue of ``init_kv_cache``.
    ``axis_size`` divides the KV heads for TP (build the global array and
    shard dim 2 over the tensor axis, or call inside shard_map).
    ``quantized=True``: int8 ``(q8, scale)`` pairs per entry, the same
    per-position-vector symmetric scheme as the contiguous cache.

    A model whose attention is latent (``cfg.latent_width``) gets the
    one-leaf pool ``{'kv': [L, num_blocks, 1, latent_width, block_size]}``
    instead: nothing to divide over a tensor axis, no int8 form yet.

    A model with window layers (``cfg.window_layers``) gets a SECOND pool
    under ``'win'``: ``{'k','v': [window_layers, window_blocks, Hkv,
    block_size, hd]}``, with block ids, a NULL block and a table of its own;
    ``Hkv`` is the window layers' own count where the model states one
    (``cfg.window_kv_heads``).  Value heads of another width than the key
    heads (``cfg.v_head_dim``): every V leaf is ``[.., block_size, value
    width]`` and every K leaf lies transposed, ``[.., key width,
    block_size]`` (module docstring, "Unequal widths")."""
    if _window_layers(cfg):
        if quantized or axis_size != 1:
            raise NotImplementedError(
                "a window pool has no int8 form and no tensor-parallel "
                "split yet (ROADMAP queue 2)")
        if window_blocks < 2:
            raise ValueError(
                f"window_blocks must be >= 2 (block 0 is the window pool's "
                f"NULL block), got {window_blocks}")
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is the reserved NULL block), "
            f"got {num_blocks}")
    width = _latent_width(cfg)
    if width:
        if quantized or axis_size != 1:
            raise NotImplementedError(
                "a latent pool has no int8 form and no tensor-parallel "
                "split yet (ROADMAP queue 2)")
        return {"kv": jnp.zeros(
            (_kv_layers(cfg), num_blocks, 1, width, block_size), cfg.dtype)}
    hkv, rem = divmod(cfg.block.kv_head_count, axis_size)
    if rem or hkv == 0:
        raise ValueError(
            f"kv_heads {cfg.block.kv_head_count} not divisible by tp "
            f"{axis_size} (whole KV heads per shard)"
        )
    pack = _kv_pack(cfg)
    if pack > 1 and (quantized or axis_size != 1):
        raise NotImplementedError(
            "a pool of heads narrower than a lane row has no int8 form and "
            "no tensor-parallel split yet (ROADMAP queue 2)")
    shape = (_kv_layers(cfg), num_blocks, hkv // pack, block_size,
             pack * cfg.block.head_dim)
    if _index_width(cfg):
        if quantized or axis_size != 1:
            raise NotImplementedError(
                "an indexed pool has no int8 form and no tensor-parallel "
                "split yet (ROADMAP queue 2)")
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                "idx": jnp.zeros((shape[0], num_blocks, 1, _index_width(cfg),
                                  block_size), cfg.dtype)}
    if quantized:
        def entry():
            return (jnp.zeros(shape, jnp.int8),
                    jnp.ones(shape[:-1], jnp.float32))
        return {"k": entry(), "v": entry()}
    vw = _value_width(cfg)

    def leaves(layers, blocks, heads):
        """One pool's K and V: of one shape, or (unequal widths) the keys
        transposed, ``[.., key_width, block_size]``."""
        k = v = (layers, blocks, heads // pack, block_size, pack * vw)
        if vw != cfg.block.head_dim:
            k = (layers, blocks, heads, cfg.block.head_dim, block_size)
        return {"k": jnp.zeros(k, cfg.dtype), "v": jnp.zeros(v, cfg.dtype)}

    pool = leaves(shape[0], num_blocks, hkv)
    if _window_layers(cfg):
        pool["win"] = leaves(_window_layers(cfg), window_blocks,
                             _window_kv_heads(cfg))
    return pool


def _kv_pack(cfg) -> int:
    """KV heads that share one row of the pool (models/hybrid.py
    ``kv_pack``: heads narrower than a lane row); 1 = a head a row."""
    return getattr(cfg, "kv_pack", 1)


def pack_heads(val: jnp.ndarray, pack: int) -> jnp.ndarray:
    """``[B, Hkv, S, hd] -> [B, Hkv / pack, S, pack * hd]``: ``pack``
    consecutive KV heads of one position side by side, as a pool of narrow
    heads keeps them."""
    B, Hkv, S, hd = val.shape
    return val.reshape(B, Hkv // pack, pack, S, hd).swapaxes(2, 3).reshape(
        B, Hkv // pack, S, pack * hd)


def _value_width(cfg) -> int:
    """A value head's width (models/hybrid.py ``v_head_dim``); every other
    family's is its key head's."""
    return getattr(cfg, "v_head_dim", 0) or cfg.block.head_dim


def _window_kv_heads(cfg) -> int:
    """The window layers' KV heads (models/hybrid.py ``window_kv_heads``);
    the other layers' where the model states none."""
    return getattr(cfg, "window_kv_heads", 0) or cfg.block.kv_head_count


def keys_transposed(ck, cv) -> bool:
    """Whether a pool's K leaf lies transposed, ``[.., key_width,
    block_size]`` beside V's ``[.., block_size, value_width]``: wherever
    the two widths differ (module docstring, "Unequal widths")."""
    first = lambda c: c[0] if isinstance(c, tuple) else c
    return first(ck).shape[-2:] != first(cv).shape[-2:]


def _window_layers(cfg) -> int:
    """The window pool's depth (models/hybrid.py kind ``W``); 0 = the model
    has one pool."""
    return getattr(cfg, "window_layers", 0)


def window_bytes(cache: Dict[str, Any]) -> int:
    """Bytes of the window layers' pool (0 where there is none)."""
    return pool_bytes(cache["win"]) if "win" in cache else 0


def window_reach(window: int, chunk: int, block_size: int) -> int:
    """The most blocks of the window pool that one sequence needs at a
    time: the table columns from the first key inside the window of a
    call's FIRST row to the call's last row, over every call the engine
    makes.  A prefill chunk at offset ``o``, a multiple of ``chunk``, reads
    keys from ``o - window + 1`` to ``o + chunk - 1``: ``(window + chunk) /
    block_size`` columns where both are whole blocks, which is asked of
    them; a decode row reaches over ``window / block_size + 1``."""
    if window % block_size or chunk % block_size:
        raise ValueError(
            f"a window pool wants window ({window}) and chunk ({chunk}) in "
            f"whole blocks of {block_size}: a slot's reach is their sum")
    return (window + chunk) // block_size


def _kv_layers(cfg) -> int:
    """The pool's depth: the layers that keep keys and values.  A hybrid
    family (models/hybrid.py) says how many of its layers do; every other
    family's layers all do."""
    return getattr(cfg, "kv_layers", cfg.nlayers)


def _latent_width(cfg) -> int:
    """What one position caches where attention is latent; 0 = keys and
    values a head."""
    return getattr(cfg, "latent_width", 0)


def _index_width(cfg) -> int:
    """What one position caches for the indexer where attention is indexed
    (the pool's ``idx`` leaf); 0 = no such leaf."""
    return getattr(cfg, "index_width", 0)


def index_bytes(cache: Dict[str, Any]) -> int:
    """Bytes of the pool's indexer-key leaf (0 where there is none)."""
    return pool_bytes({"idx": cache["idx"]}) if "idx" in cache else 0


def block_size_of(cache: Dict[str, Any]) -> int:
    """The pool's block size, tuple-safe (quantized pools store pairs); a
    latent pool's blocks lie transposed, positions last."""
    if "kv" in cache:
        return cache["kv"].shape[4]
    v = cache["v"]   # the K leaf lies transposed where the widths differ
    return (v[0] if isinstance(v, tuple) else v).shape[3]


def is_quantized(cache: Dict[str, Any]) -> bool:
    """Whether the pool's leaves are int8 ``(q8, scale)`` pairs."""
    return any(isinstance(leaf, tuple) for leaf in cache.values())


def pool_bytes(cache: Dict[str, Any]) -> int:
    """Total bytes of the pool's device buffers (k + v, quantized pairs
    included) — what the allocator's blocks actually cost in HBM.  The
    obs ``memory`` section cross-checks this against
    :func:`expected_pool_bytes`' shape math."""
    import numpy as np

    return int(sum(
        int(np.prod(leaf.shape, dtype=np.int64)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(cache)
    ))


def expected_pool_bytes(
    cfg: GPTConfig, num_blocks: int, block_size: int, axis_size: int = 1,
    quantized: bool = False, window_blocks: int = 0,
) -> int:
    """What :func:`init_paged_kv` SHOULD allocate, from shape math alone:
    ``2 * L * num_blocks * Hkv/axis_size * block_size * hd`` entries in
    ``cfg.dtype`` (int8 + f32 per-vector scale when ``quantized``).  The
    independent half of the pool-accounting cross-check.  A latent pool
    is ONE leaf of ``L * num_blocks * block_size * latent_width``; an
    indexed pool adds ``L * num_blocks * block_size * index_width``; a
    window pool ``window_layers * window_blocks * window Hkv * block_size *
    (hd + value width)`` (``2 hd`` a position and head wherever the widths
    are equal)."""
    if _latent_width(cfg):
        return (_kv_layers(cfg) * num_blocks * block_size
                * _latent_width(cfg) * jnp.dtype(cfg.dtype).itemsize)
    hkv = cfg.block.kv_head_count // axis_size
    entries = _kv_layers(cfg) * num_blocks * hkv * block_size
    hd = cfg.block.head_dim
    # a key's and a value's width together
    kv = hd + _value_width(cfg)
    if quantized:
        per_kv = entries * kv * 1 + 2 * entries * 4  # int8 q + f32 scale
    else:
        per_kv = entries * kv * jnp.dtype(cfg.dtype).itemsize
    # k and v, the indexer's key a position where attention is indexed, and
    # the window layers' k and v
    return (per_kv
            + (_kv_layers(cfg) * num_blocks * block_size * _index_width(cfg)
               + _window_layers(cfg) * window_blocks * _window_kv_heads(cfg)
               * block_size * kv) * jnp.dtype(cfg.dtype).itemsize)


def _write_blocks(tables: jnp.ndarray, offset: jnp.ndarray, S_in: int,
                  block_size: int):
    """The pool blocks that rows ``offset[b] + arange(S_in)`` fall in, as
    :func:`paged_write` rewrites them: ``(blk [B, n], src [B, n, bs],
    valid [B, n, bs])``.  Slot b's rows span at most ``n`` consecutive
    table columns from ``offset[b] // bs``; row r of its j-th block is
    position ``(offset[b] // bs + j) * bs + r``, which the call's row
    ``src`` supplies where ``valid`` and which keeps what it holds
    elsewhere.  A column past the table's width (a padded tail's
    overshoot), or one that none of the call's rows falls in, is sent to
    the NULL block; an unallocated column already names it."""
    max_blocks = tables.shape[1]
    n = (S_in + block_size - 2) // block_size + 1
    col = (offset // block_size)[:, None] + jnp.arange(n)[None, :]  # [B, n]
    src = (col * block_size - offset[:, None])[..., None] + jnp.arange(
        block_size)                                               # [B, n, bs]
    valid = (src >= 0) & (src < S_in)
    live = (col < max_blocks) & valid.any(-1)
    blk = jnp.take_along_axis(
        tables, jnp.clip(col, 0, max_blocks - 1), axis=1)
    return jnp.where(live, blk, NULL_BLOCK), jnp.clip(src, 0, S_in - 1), valid


@prof.scoped(prof.KV_WRITE)
def paged_write(c, val: jnp.ndarray, offset, *, tables: jnp.ndarray,
                layer=None, transposed: bool = False):
    """Write ``val`` [B, Hkv, S_in, hd] into layer ``layer`` of the pool
    ``c`` ([L, num_blocks, Hkv, bs, hd] or its quantized pair) at per-slot
    positions ``offset[b] + arange(S_in)`` via the block tables, and return
    the WHOLE pool.  ``layer=None``: ``c`` is one layer's ``[num_blocks,
    Hkv, bs, hd]``.

    The write is by whole blocks (:func:`_write_blocks`): the blocks the
    rows fall in are read at ``[layer, blk]``, the call's rows laid over
    them, and the blocks scattered back.  A scatter whose update is a
    whole ``[Hkv, bs, hd]`` block is contiguous in the pool AS IT LIES, so
    on a loop carry (or a donated argument) XLA updates the buffer in
    place.  The row form ``c.at[layer, blk, :, idx]`` is not: its
    ``[Hkv, hd]`` window straddles ``bs``, XLA:TPU gives such a scatter a
    pool with ``bs`` and ``Hkv`` swapped, and the paged kernel wants the
    pool as it lies, so every layer paid two copies of the pool between
    the two layouts (PERF.md section 6, PR 27).  Live slots own disjoint
    blocks, so the scatter has no racing duplicates (only the NULL block
    absorbs colliding writes, and it is never read).

    ``transposed``: ``c`` is a K leaf of a pool of unequal widths, ``[L,
    num_blocks, Hkv, hd, bs]`` (module docstring): the same whole blocks,
    a call's rows laid over them as their COLUMNS."""
    if layer is None:
        whole = paged_write(jax.tree.map(lambda a: a[None], c), val, offset,
                            tables=tables, layer=0, transposed=transposed)
        return jax.tree.map(lambda a: a[0], whole)
    if transposed:
        return _write_transposed(c, val, offset, tables, layer)
    width = (c[0] if isinstance(c, tuple) else c).shape[4]
    if width != val.shape[3]:  # narrow heads, several to a row of the pool
        val = pack_heads(val, width // val.shape[3])
    B, Hkv, S_in, hd = val.shape
    bs = (c[0] if isinstance(c, tuple) else c).shape[3]
    blk, src, valid = _write_blocks(
        tables, jnp.asarray(offset, jnp.int32), S_in, bs)
    n = blk.shape[1]

    def put(pool, rows):
        """``rows`` [B, Hkv, S_in, ...] over the blocks ``blk`` of ``pool``
        [L, nb, Hkv, bs, ...]."""
        tail = rows.shape[3:]
        pick = src.reshape((B, 1, n * bs) + (1,) * len(tail))
        new = jnp.take_along_axis(rows, pick, axis=2).reshape(
            (B, Hkv, n, bs) + tail).swapaxes(1, 2)  # [B, n, Hkv, bs, ...]
        keep = valid.reshape((B, n, 1, bs) + (1,) * len(tail))
        new = jnp.where(keep, new.astype(pool.dtype), pool[layer, blk])
        return pool.at[layer, blk.reshape(-1)].set(
            new.reshape((B * n,) + new.shape[2:]))

    if isinstance(c, tuple):
        vq, vs = _kv_quant(val)  # per-vector: identical to contiguous path
        return (put(c[0], vq), put(c[1], vs))
    return put(c, val)


@prof.scoped(prof.KV_WRITE)
def _write_transposed(pool, val, offset, tables, layer):
    """:func:`paged_write` for a transposed K leaf ``[L, nb, Hkv, hd, bs]``
    (:func:`latent_write` with a head axis): the blocks the rows fall in
    are read at ``[layer, blk]``, the call's rows ``val`` [B, Hkv, S_in, hd]
    become their columns, and the blocks are scattered back whole."""
    B, Hkv, S_in, hd = val.shape
    bs = pool.shape[4]
    blk, src, valid = _write_blocks(
        tables, jnp.asarray(offset, jnp.int32), S_in, bs)
    n = blk.shape[1]
    new = jnp.take_along_axis(
        val, src.reshape(B, 1, n * bs, 1), axis=2).reshape(B, Hkv, n, bs, hd)
    new = jnp.where(valid[:, :, None, None, :],
                    new.transpose(0, 2, 1, 4, 3).astype(pool.dtype),
                    pool[layer, blk])                 # [B, n, Hkv, hd, bs]
    return pool.at[layer, blk.reshape(-1)].set(
        new.reshape((B * n,) + new.shape[2:]))


def gather_kv(c, tables: jnp.ndarray, layer=None, transposed: bool = False):
    """Layer ``layer`` of the pool (``None``: ``c`` is one layer's) ->
    dense per-slot view [B, Hkv, max_blocks*bs, hd] (or its quantized
    pair) through the block tables: ONE gather at ``[layer, tables]``, the
    layer is never sliced out first.  Gathered index == slot-relative
    position, so the result drops straight into ``_cached_attention`` in
    place of the contiguous buffer.  ``transposed``: a K leaf ``[L, nb,
    Hkv, hd, bs]`` (unequal widths)."""
    at = (lambda a: a[tables]) if layer is None else (
        lambda a: a[layer, tables])
    if isinstance(c, tuple):
        q8, scale = c
        g = at(q8)  # [B, nb, Hkv, bs, hd]
        B, nb, Hkv, bs, hd = g.shape
        gs = at(scale).transpose(0, 2, 1, 3).reshape(B, Hkv, nb * bs)
        return (g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, nb * bs, hd), gs)
    g = at(c)
    if transposed:   # a K leaf of unequal widths: [B, nb, Hkv, hd, bs]
        g = g.swapaxes(3, 4)
    B, nb, Hkv, bs, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, nb * bs, hd)


@prof.scoped(prof.ATTEND)
def paged_attention(
    q: jnp.ndarray, ck, cv, offset, *, tables: jnp.ndarray, window=None,
    impl: str = "gather", layer=None, sm_scale: Optional[float] = None,
    sink: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Attention of q [B, H, S_in, hd] against each slot's paged context
    in layer ``layer`` of the pools ``ck`` / ``cv`` (``None``: they are one
    layer's).

    ``impl='gather'`` (the parity oracle and CPU fallback): gather the
    slot's blocks into a dense ``[B, Hkv, max_blocks*bs, hd]`` view, then
    the contiguous ``_cached_attention`` with per-slot [B] offsets — one
    attention implementation, two cache layouts, O(max context) HBM per
    call.  ``impl='pallas'``: the fused Pallas kernel
    (:func:`~..ops.paged_attention.paged_decode_attention`) walks the
    block table in-kernel — no gathered view, int8 pools dequantized
    in-register, HBM traffic bounded by the slot's live length.

    ``sm_scale``: the softmax scale (None: ``hd ** -0.5``).  A pool of
    narrow heads (its rows ``pack`` heads wide, module docstring): each
    query head is spread to the row's width, its values in the lanes of its
    own KV head and zeros in the others, so that either implementation sees
    ``Hkv / pack`` KV heads of ``pack * hd`` and computes the head's own
    scores term for term; of the row that comes back the head's lanes are
    kept.

    A pool of unequal widths (:func:`keys_transposed`: its K leaf ``[..,
    hd, bs]``): the output rows are the VALUE heads' width.  ``sink`` [H]
    float32: one more column of every row's softmax, a scalar a query head,
    which takes its share of the mass and gives no value."""
    B, H, S_in, hd = q.shape
    kt = keys_transposed(ck, cv)
    rows, width = (ck[0] if isinstance(ck, tuple) else ck).shape[-3::2]
    pack = 1 if kt else width // hd
    if pack > 1:
        if sm_scale is None:
            sm_scale = 1.0 / math.sqrt(hd)
        # query head h reads KV head h // groups, which lies in lanes
        # [e * hd, (e + 1) * hd) of its row, e = (h // groups) % pack
        Hkv = rows * pack
        lane = (jnp.arange(H) // (H // Hkv)) % pack
        own = (lane[:, None] == jnp.arange(pack)[None, :])     # [H, pack]
        q = jnp.where(own[None, :, None, :, None], q[:, :, :, None, :],
                      jnp.zeros((), q.dtype)).reshape(B, H, S_in, pack * hd)
    if impl == "pallas":
        from ..ops.paged_attention import paged_decode_attention

        out = paged_decode_attention(q, ck, cv, tables, offset, layer=layer,
                                     window=window, sm_scale=sm_scale,
                                     sink=sink)
    else:
        out = _cached_attention(
            q, gather_kv(ck, tables, layer, transposed=kt),
            gather_kv(cv, tables, layer), offset, window=window,
            sm_scale=sm_scale, sink=sink)
    if pack > 1:
        out = jnp.take_along_axis(
            out.reshape(B, H, S_in, pack, hd),
            lane[None, :, None, None, None], axis=3)[:, :, :, 0]
    return out


def _paged_cache_ops(tables: jnp.ndarray, attn_impl: str, layer,
                     sm_scale: Optional[float] = None, key_width: int = 0):
    """The ``cache_ops`` pair ``cached_block_forward`` needs to run one
    layer on the block pool instead of the contiguous buffer: the cache it
    threads through is the WHOLE pool, and ``layer`` (a python int in an
    unrolled loop, the scan's counter otherwise) is where both ops reach
    into it.  The one way a layer reaches the pool.  ``key_width``: a key
    head's width where it is NOT the value head's (0: one width): rows of
    that width are keys, and their leaf lies transposed."""
    def attend(q, ck, cv, offset, window=None, sink=None):
        return paged_attention(q, ck, cv, offset, tables=tables,
                               window=window, impl=attn_impl, layer=layer,
                               sm_scale=sm_scale, sink=sink)
    if not key_width:
        return (functools.partial(paged_write, tables=tables, layer=layer),
                attend)

    def write(c, val, offset):
        return paged_write(c, val, offset, tables=tables, layer=layer,
                           transposed=val.shape[3] == key_width)
    return write, attend


@prof.scoped(prof.KV_WRITE)
def latent_write(pool: jnp.ndarray, rows: jnp.ndarray, offset, *,
                 tables: jnp.ndarray, layer) -> jnp.ndarray:
    """:func:`paged_write` for a latent pool ``[L, nb, 1, W, bs]``: ``rows``
    [B, S_in, W] go to positions ``offset[b] + arange(S_in)`` of layer
    ``layer``, and the WHOLE pool comes back.  The same write by whole
    blocks (:func:`_write_blocks`), the block laid transposed: a block is
    read at ``[layer, blk]``, the call's rows become its columns, and it is
    scattered back whole, contiguous in the pool as it lies."""
    B, S_in, W = rows.shape
    bs = pool.shape[4]
    blk, src, valid = _write_blocks(
        tables, jnp.asarray(offset, jnp.int32), S_in, bs)
    n = blk.shape[1]
    new = jnp.take_along_axis(
        rows, src.reshape(B, n * bs, 1), axis=1).reshape(B, n, bs, W)
    new = jnp.where(valid[:, :, None, :],
                    new.swapaxes(2, 3).astype(pool.dtype),
                    pool[layer, blk, 0])                  # [B, n, W, bs]
    return pool.at[layer, blk.reshape(-1), 0].set(new.reshape(B * n, W, bs))


def _latent_cache_ops(tables: jnp.ndarray, attn_impl: str, cfg, layer):
    """:func:`_paged_cache_ops` for a latent layer: ``write(pool, rows
    [B, S_in, W], offset)`` and ``attend(q [B, H, S_in, W], pool, offset)
    -> [B, H, S_in, latent]`` (ops/mla_attention.py: the kernel, or its
    gathered oracle)."""
    from ..ops import mla_attention as M

    attend = (M.mla_paged_attention if attn_impl == "pallas"
              else M.mla_gather_attention)

    @prof.scoped(prof.ATTEND)
    def attend_layer(q, pool, offset):
        return attend(q, pool, tables, offset, latent=cfg.mla_latent,
                      sm_scale=cfg.mla_scale, layer=layer)
    return functools.partial(latent_write, tables=tables,
                             layer=layer), attend_layer


def _indexed_cache_ops(tables: jnp.ndarray, attn_impl: str, cfg, layer):
    """:func:`_paged_cache_ops` for an indexed layer (models/hybrid.py kind
    ``S``): ``(write, write_idx, attend)``.  ``write`` is the K/V pool's,
    ``write_idx(pool, rows [B, S_in, idx_dim], offset)`` the latent pool's
    op on the ``idx`` leaf, and ``attend(q, ck, cv, cidx, qi [B, J, S_in,
    idx_dim], w [B, S_in, J], offset)`` scores every cached position, keeps
    each query's ``idx_topk`` best and attends to those alone
    (ops/dsa_attention.py: three kernels, or their gathered oracle); it
    gives the output and the kept positions as bits."""
    from ..ops import dsa_attention as D

    @prof.scoped(prof.ATTEND)
    def attend(q, ck, cv, cidx, qi, w, offset):
        return D.indexed_attention(
            q, ck, cv, cidx, qi, w, tables, offset, topk=cfg.idx_topk,
            layer=layer, impl=attn_impl)
    return (functools.partial(paged_write, tables=tables, layer=layer),
            functools.partial(latent_write, tables=tables, layer=layer),
            attend)


@prof.scoped(prof.MIXER)
def _batched_rope(bcfg, positions: jnp.ndarray):
    """Per-slot rope tables: positions [B, S] -> (cos, sin) [B, 1, S,
    hd/2].  Reuses ``rope_cache`` on the flattened positions so each
    position's rotation is bitwise the table the contiguous path computes
    for it."""
    if not bcfg.rope:
        return None
    B, S = positions.shape
    cos, sin = rope_cache(
        positions.reshape(-1), bcfg.head_dim, bcfg.rope_theta,
        scaling=bcfg.rope_scaling)
    half = cos.shape[-1]
    return (cos.reshape(B, S, half)[:, None], sin.reshape(B, S, half)[:, None])


@prof.scoped(prof.HEAD)
def _select_row(h: jnp.ndarray, last_idx) -> jnp.ndarray:
    """h [B, S, D] -> [B, 1, D] at per-slot row ``last_idx`` ([B] int32);
    None = the last row (the decode case, bitwise the contiguous slice)."""
    if last_idx is None:
        return h[:, -1:, :]
    idx = jnp.clip(jnp.asarray(last_idx), 0, h.shape[1] - 1)
    return jnp.take_along_axis(h, idx[:, None, None], axis=1)


def paged_forward(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    cache: Dict[str, Any],
    tables: jnp.ndarray,
    offset: jnp.ndarray,
    axis: Optional[str] = None,
    last_idx=None,
    all_logits: bool = False,
    attn_impl: str = "gather",
) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """``forward_cached`` over the block pool: run ``tokens`` [B, S_in]
    (slot b's rows occupy global positions ``offset[b] + arange(S_in)``)
    through the cached stack, writing k/v into each slot's blocks and
    attending through its table.  Returns the updated pool and the logits
    [B, V_local] read at per-slot row ``last_idx`` (default: the last row
    — the decode case).  The layers ride a ``lax.scan`` over the stacked
    block params and a layer counter, with the pool as a carry that every
    layer updates at ``[layer, ...]`` (docs/serving.md "The pool's life");
    chunked prefill is just S_in=chunk at a running offset — one
    implementation, both phases, either layout.

    ``all_logits=True`` returns the per-position logits [B, S_in,
    V_local] instead — the multi-position evaluation the speculative
    verify step needs (the model's distribution at EVERY drafted
    position, one paged-attention pass).

    ``attn_impl``: ``'gather'`` (table-gather then dense attention — the
    parity oracle) or ``'pallas'`` (the fused in-kernel table walk,
    docs/serving.md "Paged attention kernel")."""
    bcfg = cfg.block
    S_in = tokens.shape[1]
    offset = jnp.asarray(offset, jnp.int32)
    positions = offset[:, None] + jnp.arange(S_in)[None, :]
    h = _embed_at(params, tokens, positions, axis)
    rope = _batched_rope(bcfg, positions)

    def body(carry, xs):
        # the pool is a CARRY, whole: as the scan's xs / ys every layer
        # would slice its share out of the stack and write it back
        hc, ck, cv = carry
        lp, li = xs
        return cached_block_forward(
            lp, hc, bcfg, ck, cv, offset, axis=axis, rope=rope,
            cache_ops=_paged_cache_ops(tables, attn_impl, li)), None

    (h, ck, cv), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.nlayers)))
    if all_logits:
        return {"k": ck, "v": cv}, gpt_head(params, h, axis, False,
                                            eps=cfg.norm_eps)
    logits = gpt_head(params, _select_row(h, last_idx), axis, False,
                      eps=cfg.norm_eps)
    return {"k": ck, "v": cv}, logits[:, 0, :]


def _cp_paged_cache_ops(tables: jnp.ndarray, cp_axis: str, attn_impl: str,
                        prefill: bool, layer: int):
    """``cache_ops`` pair running layer ``layer`` of
    ``cached_block_forward`` on a pool whose block dim is sharded over
    ``cp_axis`` (ops/ring_paged.py), the pool threaded whole: the write
    ring completes the chunk's pool write BEFORE attend runs (the pair is
    called write-then-attend), so the attend ring only ever rotates pool
    slices.  ``prefill`` is the trace-time phase flag (S_in of the FULL
    chunk > 1) — the ring ops cannot infer it from their operand shapes
    because a ``chunk == cp`` sub-chunk is one row, like decode."""
    from ..ops.ring_paged import ring_paged_attend, ring_paged_write

    @prof.scoped(prof.KV_WRITE)
    def write(c, val, offset):
        return ring_paged_write(c, val, offset, tables=tables, layer=layer,
                                cp_axis=cp_axis, prefill=prefill)

    @prof.scoped(prof.ATTEND)
    def attend(q, ck, cv, offset, window=None):
        return ring_paged_attend(q, ck, cv, offset, tables=tables,
                                 layer=layer, cp_axis=cp_axis, window=window,
                                 impl=attn_impl, prefill=prefill)
    return write, attend


def cp_paged_forward(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    cache: Dict[str, Any],
    tables: jnp.ndarray,
    offset: jnp.ndarray,
    *,
    cp_axis: str,
    axis: Optional[str] = None,
    last_idx=None,
    attn_impl: str = "gather",
) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """:func:`paged_forward` across a ``context`` mesh axis — ring paged
    prefill (ops/ring_paged.py).  Call inside shard_map with the pool's
    block dim sharded over ``cp_axis`` and everything else (params,
    tokens, tables, offsets) replicated along it.

    Prefill (``S_in = chunk``, ``chunk % cp == 0``): rank r embeds and
    projects ONLY its sub-chunk rows ``[r*Csub, (r+1)*Csub)``; per layer
    the write ring lands every row in its owner's pool slice and the
    attend ring accumulates each rank's rows against all slices.  The
    per-slot head row lives on exactly one rank — its logits are selected
    by mask and ``psum`` over ``cp_axis`` makes them replicated, so
    sampling stays identical on every rank.  Decode (``S_in = 1``): every
    rank runs the same row, attends its local slice, and an exact
    pmax/psum logsumexp combine replicates the output — ONE compiled
    decode program, no extra signatures.

    The layer loop is python-unrolled (vs ``lax.scan`` in
    :func:`paged_forward`) so every ring hop is a distinct HLO
    ``collective-permute`` — the comm ledger prices each hop instead of
    undercounting a while body (the PR-3/PR-8 unrolled-ppermute lineage;
    tests/test_cp_prefill.py asserts the per-hop count)."""
    bcfg = cfg.block
    cp = _axis_size(cp_axis)
    S_in = tokens.shape[1]
    offset = jnp.asarray(offset, jnp.int32)
    decode = S_in == 1
    if decode or cp == 1:
        my_tokens = tokens
        positions = offset[:, None] + jnp.arange(S_in)[None, :]
    else:
        if S_in % cp:
            raise ValueError(
                f"cp prefill needs the chunk ({S_in}) divisible by the "
                f"context axis size ({cp})")
        sub = S_in // cp
        r = jax.lax.axis_index(cp_axis)
        my_tokens = jax.lax.dynamic_slice_in_dim(
            tokens, r * sub, sub, axis=1)
        positions = offset[:, None] + r * sub + jnp.arange(sub)[None, :]
    h = _embed_at(params, my_tokens, positions, axis)
    rope = _batched_rope(bcfg, positions)
    ck, cv = cache["k"], cache["v"]
    for li in range(cfg.nlayers):  # unrolled: one HLO permute per hop
        lp = jax.tree_util.tree_map(lambda a: a[li], params["blocks"])
        h, ck, cv = cached_block_forward(
            lp, h, bcfg, ck, cv, offset, axis=axis, rope=rope,
            cache_ops=_cp_paged_cache_ops(
                tables, cp_axis, attn_impl, prefill=not decode, layer=li))
    new_cache = {"k": ck, "v": cv}

    if decode or cp == 1:
        # decode h is replicated over cp (psum-combined attends on
        # replicated inputs); the head needs no cross-rank fixup
        logits = gpt_head(params, _select_row(h, last_idx), axis, False,
                          eps=cfg.norm_eps)
        return new_cache, logits[:, 0, :]
    sub = S_in // cp
    r = jax.lax.axis_index(cp_axis)
    li_idx = jnp.asarray(last_idx, jnp.int32)
    mine = (li_idx >= r * sub) & (li_idx < (r + 1) * sub)
    sel = _select_row(h, jnp.clip(li_idx - r * sub, 0, sub - 1))
    logits = gpt_head(params, sel, axis, False, eps=cfg.norm_eps)
    logits = jnp.where(mine[:, None, None], logits, 0.0)
    logits = jax.lax.psum(logits, cp_axis)
    return new_cache, logits[:, 0, :]


def paged_forward_moe(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: GPTConfig,
    cache: Dict[str, Any],
    tables: jnp.ndarray,
    offset: jnp.ndarray,
    axis: Optional[str] = None,
    last_idx=None,
    ep_axis: Optional[str] = None,
    all_logits: bool = False,
    attn_impl: str = "gather",
    moe_stats: bool = False,
) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """:func:`paged_forward` for the MoE family (heterogeneous block list,
    expert FFN every moe_every-th block) — the same exact no-drop serving
    dispatch as ``forward_cached_moe`` (its docstring has the semantics:
    ragged grouped GEMMs when ``ep_axis`` is None, EP-sharded exchange at
    no-drop capacity when set), attending through the block tables.
    ``all_logits=True``: per-position logits, as in :func:`paged_forward`;
    ``attn_impl`` as in :func:`paged_forward` (the MoE families ride the
    same kernel — attention is family-independent).

    ``moe_stats=True`` returns ``(cache, logits, moe_metrics)`` where
    ``moe_metrics`` sums per-expert routed-token counts over the MoE layers
    — the engine's live expert-load signal.
    """
    import dataclasses as _dc

    from ..models.gpt_moe import moe_layer_config
    from ..parallel.moe import moe_forward, moe_serve_forward

    bcfg = cfg.block
    mcfg = moe_layer_config(cfg)
    mcfg = _dc.replace(
        mcfg,
        capacity_factor=max(mcfg.capacity_factor,
                            mcfg.num_experts / mcfg.top_k),
    )
    S_in = tokens.shape[1]
    offset = jnp.asarray(offset, jnp.int32)
    positions = offset[:, None] + jnp.arange(S_in)[None, :]
    h = _embed_at(params, tokens, positions, axis)
    rope = _batched_rope(bcfg, positions)

    collected = []  # per-MoE-layer metrics dicts (moe_stats)
    if ep_axis is None:
        def moe_ffn(p, hh):
            out = moe_serve_forward(
                p["moe"], hh, mcfg, return_metrics=moe_stats)
            if moe_stats:
                z, met = out
                collected.append(met)
                return z
            return out
    else:
        def moe_ffn(p, hh):
            out = moe_forward(
                p["moe"], hh, mcfg, ep_axis=ep_axis, causal=bcfg.causal,
                return_metrics=moe_stats)
            if moe_stats:
                z, _aux, met = out
                collected.append(met)
                return z
            z, _aux = out
            return z

    ck, cv = cache["k"], cache["v"]
    for i, bp in enumerate(params["blocks"]):
        h, ck, cv = cached_block_forward(
            bp, h, bcfg, ck, cv, offset,
            axis=axis, rope=rope, ffn=moe_ffn if "moe" in bp else None,
            cache_ops=_paged_cache_ops(tables, attn_impl, i),
        )
    cache = {"k": ck, "v": cv}
    metrics = None
    if moe_stats:
        # sum routed-token counts over the MoE layers, mean the drop rate
        metrics = {
            "expert_tokens": sum(m["expert_tokens"] for m in collected),
            "dropped_token_rate": sum(
                m["dropped_token_rate"] for m in collected
            ) / max(len(collected), 1),
        }
    if all_logits:
        logits = gpt_head(params, h, axis, False, eps=cfg.norm_eps)
    else:
        logits = gpt_head(params, _select_row(h, last_idx), axis, False,
                          eps=cfg.norm_eps)[:, 0, :]
    if moe_stats:
        return cache, logits, metrics
    return cache, logits


def paged_forward_hybrid(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg,
    cache: Dict[str, Any],
    state: Dict[str, Any],
    tables: jnp.ndarray,
    offset: jnp.ndarray,
    n_valid: jnp.ndarray,
    rows: Optional[jnp.ndarray] = None,
    last_idx=None,
    attn_impl: str = "gather",
):
    """:func:`paged_forward` for the hybrid family (models/hybrid.py): the
    attention layers write and attend through the block tables, the Mamba
    layers read and write the recurrent ``state`` (``init_state``: one
    ``[num_slots, ...]`` array a Mamba layer), and a convolved attention
    layer does both: keys and values through the tables, and its tail (the
    rows before a position) in ``state`` beside them.  ``n_valid`` [B]: how
    many of each row's positions are real; the rest advance no state.

    ``rows`` None: row b of ``tokens`` IS slot b (the decode call), the
    state is updated where it lies.  Otherwise ``rows`` [B] int32 names the
    slot whose state each row carries (the compact prefill call): those
    slots' state is gathered, advanced and scattered back; a row that
    names no slot (``rows[b] >= num_slots``, padding) reads some slot's
    state, advances nothing (its ``n_valid`` is 0) and writes nowhere.  A
    row at position 0 has no history: it starts from the ZERO state
    whatever its slot held, which is how admission, preemption and a fault
    requeue restart a sequence (recompute, as for KV) with no reset call.

    A model with window layers: ``tables`` is the PAIR ``(tables, window
    tables)``, one table a pool; the window layers write and attend through
    the second, whose columns are absolute as the first's are (the engine
    hands a block that fell behind the window on to a column ahead:
    docs/serving.md "Two pools").

    Returns ``(cache, state, logits [B, V], moe_metrics)``."""
    from ..models.hybrid import hybrid_paged_forward

    offset = jnp.asarray(offset, jnp.int32)
    window_ops = None
    # unequal widths: the ops are told which rows are keys
    wide = ({"key_width": cfg.head_dim}
            if _value_width(cfg) != cfg.block.head_dim else {})
    if _window_layers(cfg):
        tables, wtables = tables
        window_ops = functools.partial(_paged_cache_ops, wtables, attn_impl,
                                       sm_scale=cfg.attn_scale, **wide)
    if _latent_width(cfg):
        ops = functools.partial(_latent_cache_ops, tables, attn_impl, cfg)
    elif _index_width(cfg):
        ops = functools.partial(_indexed_cache_ops, tables, attn_impl, cfg)
    else:
        ops = functools.partial(_paged_cache_ops, tables, attn_impl,
                                sm_scale=cfg.attn_scale, **wide)
    mine = state
    if rows is not None:
        def own(a):
            a = a.at[rows].get(mode="clip")
            fresh = (offset == 0).reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(fresh, jnp.zeros((), a.dtype), a)

        with jax.named_scope(prof.STATE):
            mine = jax.tree.map(own, state)
    cache, mine, logits, metrics = hybrid_paged_forward(
        params, tokens, cfg, cache, mine, n_valid, ops, offset,
        last_idx=last_idx, window_ops=window_ops)
    if rows is not None:
        with jax.named_scope(prof.STATE):
            mine = jax.tree.map(
                lambda a, new: a.at[rows].set(new, mode="drop"), state, mine)
    return cache, mine, logits, metrics


def copy_blocks(cache: Dict[str, Any], src: jnp.ndarray,
                dst: jnp.ndarray) -> Dict[str, Any]:
    """Copy block contents ``src[i] -> dst[i]`` along the pool's block dim
    (dim 1 of every leaf, quantized pairs included) — the device half of
    copy-on-write.  The ids name blocks of the pool that keeps every
    position; a window pool (``cache['win']``, ids of its own) is handed
    back as it came.  ``src``/``dst`` are fixed-width int32 vectors so the
    copy is ONE compiled program whatever blocks an admission wave needs
    copied; unused lanes are padded ``NULL -> NULL`` (the write-off
    block's contents are never read, so colliding pad writes are
    harmless)."""
    def cp(leaf):
        return leaf.at[:, dst].set(leaf[:, src])
    out = jax.tree.map(cp, {k: v for k, v in cache.items() if k != "win"})
    if "win" in cache:
        out["win"] = cache["win"]
    return out


def migrate_blocks(
    src_cache: Dict[str, Any],
    dst_cache: Dict[str, Any],
    src_ids: jnp.ndarray,
    dst_ids: jnp.ndarray,
    compress: bool = False,
) -> Dict[str, Any]:
    """Cross-pool block copy: ``dst[:, dst_ids[i]] = src[:, src_ids[i]]``
    for every leaf pair — :func:`copy_blocks` generalized from one pool to
    two, the device half of a prefill→decode handoff or any cross-replica
    KV migration (serving/router.py).  ``src_ids``/``dst_ids`` are
    fixed-width int32 lane vectors so the copy is ONE compiled program per
    (src, dst) pool pair whatever a migration needs moved; unused lanes
    are padded ``NULL -> NULL`` (the write-off block is never read, so
    colliding pad writes are harmless).  Returns the updated dst cache;
    the src cache is only read (it is the source engine's own buffer: run
    this before that engine's next device call, which donates it,
    ``ServingEngine.export_slot``).

    ``compress=True`` models the int8 WIRE format of a DCN-crossing
    transfer on an fp pool: the payload is quantized per position-vector
    (the ``_kv_quant`` scheme — exactly what an int8 block ring would
    serialize) and dequantized into the destination's dtype, so the
    destination holds what the compressed wire would have delivered.
    Quantized ``(q8, scale)`` pools are ALREADY the wire format — their
    pairs copy verbatim and ``compress`` changes nothing (bit-exact
    migration either way)."""
    # a quantized pool's leaves are (q8, scale) pairs — already the wire
    # format; its f32 scale sideband must never be re-quantized
    compress = compress and not is_quantized(dst_cache)

    def cp(s_leaf, d_leaf):
        payload = s_leaf[:, src_ids]
        if compress:
            q, scale = _kv_quant(payload)
            payload = q.astype(jnp.float32) * scale[..., None]
        return d_leaf.at[:, dst_ids].set(payload.astype(d_leaf.dtype))

    return jax.tree.map(cp, src_cache, dst_cache)


def migration_wire_bytes(
    cfg: GPTConfig, n_blocks: int, block_size: int, axis_size: int = 1,
    quantized: bool = False, compressed: bool = False,
) -> int:
    """Bytes a migration of ``n_blocks`` pool blocks puts on the wire:
    the k+v payload of the blocks in the pool's storage format
    (``quantized`` pools ship their int8 pairs verbatim), or the int8
    ``(q8, scale)`` wire format when ``compressed`` — the quantity the
    router prices through ``CommModel`` and reports as
    ``migration_bytes``."""
    if n_blocks <= 0:
        return 0
    return expected_pool_bytes(
        cfg, n_blocks, block_size, axis_size=axis_size,
        quantized=quantized or compressed)


def chain_block_hashes(tokens, block_size: int) -> List[Any]:
    """Per-full-block content hashes, chained from position 0 (vLLM
    style): ``h_i = H(h_{i-1}, tokens[i*bs:(i+1)*bs])``, so a hash names
    a block's contents AND everything before it — equal hashes mean equal
    KV, which is what makes mapping a matched block into a new table
    sound.  Host-side, prompt tokens only (full blocks; a trailing
    partial block is never registered)."""
    h: Any = 0
    out: List[Any] = []
    for i in range(len(tokens) // block_size):
        h = hash((h, tuple(
            int(t) for t in tokens[i * block_size:(i + 1) * block_size])))
        out.append(h)
    return out


class BlockAllocator:
    """Host-side free-list over a pool's blocks (block 0 reserved as the
    NULL block).  LIFO reuse keeps recently-freed blocks hot.  Pure
    python — allocation happens between compiled steps and only ever
    rewrites int32 tables, never device buffers.

    **Refcounts + prefix cache** (vLLM automatic-prefix-caching lineage):
    every in-use block carries a refcount.  :meth:`share` maps an
    already-resident block into another slot's table (refcount + 1) so a
    shared prompt prefix is prefilled ONCE per content, not once per
    request; :meth:`free` decrements and only a block's LAST owner
    actually releases it.  :meth:`register` binds a block to a content
    hash (the engine chains hashes over FULL token blocks); a released
    registered block is RETAINED on a refcount-0 cached LRU instead of
    the free list, so its KV survives for the next request with the same
    prefix.  :meth:`alloc` evicts cached blocks LRU-first, and ONLY under
    pressure (the free list alone cannot cover the request) — eviction is
    observable via :meth:`pop_evicted` / ``cache_evictions``.
    Conservation under sharing becomes ``unique-in-use + cached + free ==
    usable`` with refcount-weighted ownership (:meth:`audit`)."""

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is reserved), "
                f"got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        #: block -> refcount (> 0 == in use; a block shared by k slots
        #: carries refcount k and is freed k times before release)
        self._ref: Dict[int, int] = {}
        #: refcount-0 RETAINED blocks, insertion order == LRU order
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        self._hash_of: Dict[int, Any] = {}   # block -> content hash
        self._by_hash: Dict[Any, int] = {}   # content hash -> block
        self._evicted: List[int] = []        # since last pop_evicted()
        self.cache_evictions = 0
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_cached(self) -> int:
        """Refcount-0 blocks retained for prefix reuse (reclaimable)."""
        return len(self._cached)

    @property
    def n_usable(self) -> int:
        """Allocatable blocks (pool minus the NULL block)."""
        return self.num_blocks - 1

    @property
    def in_use(self) -> int:
        """UNIQUE blocks with a live owner (shared blocks count once)."""
        return len(self._ref)

    def utilization(self) -> float:
        return self.in_use / self.n_usable

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` blocks, or None when the pool can't cover the request
        (the engine's admission back-pressure signal — nothing is
        partially allocated).  Free blocks are preferred; only when they
        fall short are refcount-0 cached blocks evicted, LRU first (their
        hashes drop out of the index — the prefix is gone)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free) + len(self._cached):
            return None
        while len(self._free) < n:
            b, _ = self._cached.popitem(last=False)  # LRU
            self._drop_hash(b)
            self._free.append(b)
            self._evicted.append(b)
            self.cache_evictions += 1
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        self.peak_in_use = max(self.peak_in_use, len(self._ref))
        return blocks

    def pop_evicted(self) -> List[int]:
        """Blocks evicted from the prefix cache since the last call (the
        engine turns them into ``cache_evict`` events)."""
        out, self._evicted = self._evicted, []
        return out

    def _drop_hash(self, b: int) -> None:
        h = self._hash_of.pop(b, None)
        if h is not None and self._by_hash.get(h) == b:
            del self._by_hash[h]

    def share(self, block: int) -> None:
        """Map an already-resident block into another owner's table:
        refcount + 1 for an in-use block; a cached (refcount-0) block is
        revived off the LRU.  Raises on non-resident blocks — sharing a
        freed block would be a use-after-free by construction."""
        b = int(block)
        if b in self._ref:
            self._ref[b] += 1
        elif b in self._cached:
            del self._cached[b]
            self._ref[b] = 1
        else:
            raise ValueError(f"share of non-resident block {b}")
        self.peak_in_use = max(self.peak_in_use, len(self._ref))

    def register(self, block: int, content_hash: Any) -> bool:
        """Bind an in-use block to a content hash so future
        :meth:`match` calls can find it.  First registration wins: when
        the hash already names a DIFFERENT resident block (two slots
        prefilled the same prompt concurrently), the newcomer stays
        unregistered and frees normally.  Returns True when registered."""
        b = int(block)
        if b not in self._ref:
            raise ValueError(f"register of block {b} not in use")
        if content_hash in self._by_hash and self._by_hash[content_hash] != b:
            return False
        self._by_hash[content_hash] = b
        self._hash_of[b] = content_hash
        return True

    def match(self, hashes: Sequence[Any]) -> List[int]:
        """Longest prefix of ``hashes`` whose blocks are resident (in use
        or cached), in order — the admission-time prefix lookup.  Pure
        read: :meth:`share` is what pins the result."""
        out: List[int] = []
        for h in hashes:
            b = self._by_hash.get(h)
            if b is None or (b not in self._ref and b not in self._cached):
                break
            out.append(b)
        return out

    def free(self, blocks: List[int]) -> None:
        """Release one ownership reference per block.  A shared block
        survives until its LAST owner frees it; at refcount 0 a
        registered block moves to the cached LRU (prefix retained), an
        unregistered one returns to the free list."""
        for b in blocks:
            b = int(b)
            r = self._ref.get(b)
            if b == NULL_BLOCK or r is None:
                raise ValueError(
                    f"freeing block {b} not handed out by this allocator")
            if r > 1:
                self._ref[b] = r - 1
                continue
            del self._ref[b]
            if b in self._hash_of:
                self._cached[b] = None  # MRU end of the LRU
            else:
                self._free.append(b)

    # ------------------------------------------------- conservation audit

    def audit(self, slot_tables) -> Dict[str, Any]:
        """Block-conservation audit against the slots' owned-block lists
        (the engine calls this every tick; ``tests`` call it after every
        lifecycle transition).  ``slot_tables`` is one block sequence per
        LIVE slot — the host-side ownership records the allocator's
        refcounts must agree with exactly:

        - ``orphaned``: in-use blocks no slot references (a leak — e.g.
          a retirement that forgot to free);
        - ``unknown``: blocks a slot references that the allocator says
          are free or cached (a use-after-free — the slot would read
          another request's cache once the block is rehanded out);
        - ``shared``: refcount-weighted ownership violated — the number
          of slots referencing an in-use block differs from its
          refcount (legitimate prefix sharing has them EQUAL; a scatter
          collision needs an over-reference, which lands here);
        - ``conserved``: ``unique in_use + cached + free == usable``
          with disjoint free / cached / in-use sets and no NULL entry.

        ``ok`` iff all four are clean.  Pure host arithmetic, O(blocks).
        """
        import collections as _c

        counts = _c.Counter(
            int(b) for t in slot_tables for b in t if int(b) != NULL_BLOCK)
        refset = set(counts)
        free_set = set(self._free)
        ref_keys = set(self._ref)
        cached_set = set(self._cached)
        report = {
            "orphaned": sorted(ref_keys - refset),
            "unknown": sorted(refset - ref_keys),
            "shared": sorted(
                b for b, c in counts.items()
                if b in self._ref and c != self._ref[b]),
            "conserved": (
                len(self._ref) + len(self._cached) + len(self._free)
                == self.n_usable
                and len(free_set) == len(self._free)
                and not (free_set & ref_keys)
                and not (free_set & cached_set)
                and not (cached_set & ref_keys)
                and NULL_BLOCK not in free_set
                and NULL_BLOCK not in ref_keys
                and NULL_BLOCK not in cached_set
            ),
            "in_use": self.in_use,
            "n_free": self.n_free,
            "n_cached": self.n_cached,
        }
        report["ok"] = (
            report["conserved"]
            and not report["orphaned"]
            and not report["unknown"]
            and not report["shared"]
        )
        return report

    def reclaim(self, blocks) -> List[int]:
        """Force-return ``blocks`` to the free list whatever state they are
        in — the self-healing half of :meth:`audit` (``free`` raises on
        exactly the inconsistencies a fault creates).  Refcounts, cache
        membership, and hash registrations are all discarded.  Returns the
        blocks actually recovered; NULL and already-free blocks are
        no-ops."""
        healed = []
        free_set = set(self._free)
        for b in blocks:
            b = int(b)
            if b == NULL_BLOCK or not (0 < b < self.num_blocks):
                continue
            self._ref.pop(b, None)
            self._cached.pop(b, None)
            self._drop_hash(b)
            if b not in free_set:
                self._free.append(b)
                free_set.add(b)
                healed.append(b)
        return healed
