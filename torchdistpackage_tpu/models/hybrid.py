"""The hybrid family: a layer stack given by a pattern string, in which a
layer is ONE mixer and not attention + MLP (the ``nemotron_h`` shape):

- ``M``  a Mamba-2 mixer (:func:`mamba2_mixer`): a recurrent state of
  ``[heads, head_dim, state]`` floats and the last ``conv_kernel - 1`` rows
  of the convolution's input per sequence, and no keys at all;
- ``*``  grouped-query attention with NO positional encoding (the Mamba
  layers carry order), through the same cache ops (block pool, paged
  kernel) as every other family;
- ``E``  the expert layer (:func:`~..parallel.moe.moe_serve_forward` with
  ``score='sigmoid'``, a shared expert and a held range of experts;
  ``moe_act`` 'relu2' experts in a latent width, or gated 'swiglu' ones);
- ``L``  latent attention (:func:`latent_attention_mixer`): every head's
  keys and values are up-projections of ONE cached row a position, the
  normed latent and a rotated key shared by all heads, and attention runs
  in that latent (the absorbed form), over a block pool of its own shape;
- ``D``  a dense gated MLP (SwiGLU, gate and up side by side);
- ``C``  compressed convolutional attention (:func:`cca_mixer`): queries
  and keys are projected DOWN into ``nheads + kv_heads`` heads of
  ``head_dim``, mixed by two short causal convolutions over the sequence
  before they are normed, rotated (the leading ``cca_rope`` dims of a head)
  and cached; half the value heads are the position BEFORE's.  The first
  layer that keeps both: keys and values in the block pool (the ``*``
  layers' pool and kernels), and a tail a sequence (the rows the next
  position's convolutions and shifted value need);
- ``W``  WINDOWED grouped-query attention, rotated (rope over the whole
  head, half-split pairs): a query at ``t`` reads the keys in ``(t -
  window, t]`` and nothing before them.  It stands beside ``*`` layers in
  one model (three window layers to one global layer, say), and the two
  kinds keep different amounts of cache: the model has TWO block pools (of
  one block shape, or of two: below), the ``*`` layers' holding every
  position of a sequence and the ``W`` layers' the last ``window`` (+ a
  call's rows) alone
  (serving/paged_cache.py, docs/serving.md "Two pools").  Both kinds take
  two optional sets of leaves: ``wg`` (an output gate, ``y = (o *
  sigmoid(x W_g)) W_o``) and ``q_norm`` / ``k_norm`` (a learned RMSNorm
  over each query head and each key head, before the rotation).  What a
  model may state further of the two kinds, each defaulting to the above
  (MiMo-V2's block states all of it): value heads NARROWER than key heads
  (``v_head_dim``: 64 query heads of 192 over values of 128; the three
  projections are then ONE leaf ``wqkv``, and the pool lays such keys
  transposed, serving/paged_cache.py "Unequal widths"); ANOTHER number of
  KV heads in the window layers (``window_kv_heads``: the two pools then
  have two block shapes); rotation of a head's leading ``rope_dims`` alone;
  a theta a kind (``rope_theta`` the ``W`` layers', ``global_rope_theta``
  the ``*`` layers', which are then rotated too); ``value_scale`` (the
  value states are multiplied by it before they are cached, rounded once);
  and a ``sink`` leaf ``[nheads]`` float32 on a layer of either kind: one
  more column of every row's softmax, ``p_j = exp(s_j) / (exp(sink_h) +
  sum_i exp(s_i))``, which takes its share of the mass and gives no value
  (the online softmax of both paged walks starts at ``(m, l, acc) =
  (sink_h, 1, 0)``, ops/paged_attention.py);
- ``S``  indexed (sparse) attention (:func:`indexed_attention_mixer`):
  rotated GQA with a learned norm a head, behind an INDEXER that scores
  every cached position with a second, small key (one ``idx_dim`` row a
  position, in a pool leaf of its own beside K and V), keeps the
  ``idx_topk`` best and lets all query heads attend to those alone.  The
  first layer here with positions of the ordinary kind: rope over the whole
  head, by three position axes (``mrope_section``) that are equal for text.

A layer is ``x <- x + mixer(RMSNorm(x))``, so a pre-norm block of
attention + FFN is two layers here (``"LD"``, ``"LE"``, ``"CE"``); a layer
with a ``post_norm`` leaf norms what its mixer gives as well, ``x <- x +
RMSNorm_post(mixer(RMSNorm(x)))`` (a block of four norms is two such
layers), and one with a ``res`` leaf mixes by four learned vectors
instead, ``x <- a_h x + b_h + a_y y + b_y``.  A published block of a mixer
and an MLP behind it, each under a SCALED residual (``h <- h + r
mixer(RMSNorm(h))``, then ``h <- h + r mlp(RMSNorm(h))``: Granite 4.0-H's
``residual_multiplier``), is two one-mixer layers, ``"MD"`` or ``"*D"``,
with ``residual_scale`` = r on both.  Three more constants a model may
state, each defaulting to what every other model computes:
``embed_scale`` (the embedding's rows are multiplied by it), ``attn_scale``
(the softmax scale of the ``*`` / ``W`` / ``C`` layers where it is not
``head_dim ** -0.5``; it reaches the paged kernel and its gathered oracle as
their ``sm_scale``, so the query is multiplied by nothing and rounded no
further) and ``logits_scale`` (the head's logits are multiplied by it).
Heads NARROWER than a lane tile (``head_dim`` 64 under 128 lanes): the pool
lays ``kv_pack`` KV heads side by side in one 128-wide row
(:attr:`HybridConfig.kv_pack`, serving/paged_cache.py "Narrow heads"), since
a TPU holds a 64-wide minor dimension at 128 and the paged kernel cannot
slice it.  A final RMSNorm, then the head: ``params["head"]``
[D, V], or the embedding table itself where the tree has no such leaf (a
tied head).  The biases are the convolutions', the network router's
(``moe_score='mlp'``) and the ``res`` leaves'; no projection has one.
``params["layers"]`` is a list of per-layer dicts (as ``gpt_moe.py`` lists
its blocks), each ``{"norm": ..., <the mixer's leaves>}``; the kind of
layer ``i`` is ``cfg.pattern[i]``.

This is the SERVING path (:func:`hybrid_paged_forward`, driven by
``ServingEngine``): the engine keeps what a layer carries from position to
position beside its paged KV pool (:func:`init_state`), one array a layer
that has any with one row a slot.  Training this family (a chunked scan
with a backward, the router's auxiliary loss, a backward through the
convolved keys) is ROADMAP queue 2 A1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.moe import MoEConfig, _unbiased_act, moe_serve_forward
from ..parallel.tensor_parallel import TransformerConfig, dense
from ..parallel.tensor_parallel.layers import (
    apply_rope,
    layer_norm,
    rms_norm,
    rope_cache,
)
from ..utils import profiling as prof

PyTree = Any
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    dim: int
    #: one character a layer: 'M' Mamba-2 | '*' attention | 'E' experts |
    #: 'L' latent attention | 'D' dense gated MLP | 'C' convolved attention
    #: | 'S' indexed attention | 'W' windowed, rotated attention
    pattern: str
    max_seq: int
    nheads: int
    kv_heads: int
    #: a head's width ('*', 'C', 'S'), which the pool takes from :attr:`block`;
    #: 0: the heads tile the model's width, ``dim / nheads``
    head_dim: int = 0
    # Mamba-2 ('M'): d_inner = mamba_heads x mamba_head_dim
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    #: prefill computes the recurrence in chunks of this many positions
    ssm_chunk: int = 128
    # latent MoE: ``moe_experts`` router outputs, of which ``moe_held``
    # ``(first, count)`` live here (None = all)
    moe_experts: int = 0
    moe_held: Optional[Tuple[int, int]] = None
    moe_top_k: int = 2
    moe_latent: Optional[int] = None
    moe_ffn: int = 0
    moe_shared_ffn: int = 0
    moe_routed_scale: float = 1.0
    #: the experts' (and the shared expert's) activation: 'relu2' | 'swiglu'
    moe_act: str = "relu2"
    #: the router: 'sigmoid' | 'softmax' (probabilities over all experts,
    #: the top k renormalised) | 'mlp' (a network with a stream of its own
    #: from expert layer to expert layer, ``parallel.moe._mlp_route``,
    #: ``moe_router_hidden`` wide)
    moe_score: str = "sigmoid"
    moe_router_hidden: int = 0
    # convolved attention ('C'): the two convolutions' kernel sizes over
    # the sequence, and how many leading dims of a head rope turns
    cca_time0: int = 2
    cca_time1: int = 2
    cca_rope: int = 0
    # latent attention ('L'): a query head is ``mla_nope + mla_rope`` wide,
    # a value head ``mla_v``; a position caches ``mla_latent + mla_rope``
    mla_latent: int = 0
    mla_nope: int = 0
    mla_rope: int = 0
    mla_v: int = 0
    # indexed attention ('S'): the indexer's query heads and their width
    # (ONE key of that width a position), how many positions a query keeps,
    # how many leading dims of an indexer head rope turns, and how a
    # head's ``head_dim / 2`` frequency pairs are dealt to the three
    # position axes (temporal, height, width; None: plain rope)
    idx_heads: int = 0
    idx_dim: int = 0
    idx_topk: int = 0
    idx_rope: int = 0
    mrope_section: Optional[Tuple[int, ...]] = None
    #: windowed attention ('W'): the keys a query reads, itself included
    window: int = 0
    #: a VALUE head's width in the '*' / 'W' layers (0: ``head_dim``, the
    #: key's); narrower values: one ``wqkv`` leaf, keys transposed in the pool
    v_head_dim: int = 0
    #: the 'W' layers' KV heads (0: ``kv_heads``, the '*' layers')
    window_kv_heads: int = 0
    #: leading dims of a query / key head that a '*' / 'W' layer rotates
    #: (0: the whole head)
    rope_dims: int = 0
    #: the '*' layers' rope theta (None: they carry no positions);
    #: ``rope_theta`` is the 'W' layers'
    global_rope_theta: Optional[float] = None
    #: what the '*' / 'W' layers' value states are multiplied by before they
    #: are cached (1: as projected)
    value_scale: float = 1.0
    #: what the embedding's rows are multiplied by (1: as they lie)
    embed_scale: float = 1.0
    #: what every layer's output is multiplied by before it joins the
    #: residual stream, ``h <- h + residual_scale * y`` (1: as it is)
    residual_scale: float = 1.0
    #: the softmax scale of the '*' / 'W' / 'C' layers (None: ``head_dim **
    #: -0.5``); handed to the kernel as its ``sm_scale``, never folded into q
    attn_scale: Optional[float] = None
    #: what the head's logits are multiplied by (1: as they are)
    logits_scale: float = 1.0
    rope_theta: float = 10000.0
    #: a rope-scaling dict as ``rope_cache`` takes it (yarn), or None
    rope_scaling: Optional[Dict[str, Any]] = None
    #: the dense gated MLP's width ('D')
    dense_ffn: int = 0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: the recurrent state's precision (the convolution's rows keep ``dtype``)
    state_dtype: Any = jnp.float32
    # what ``ServingEngine`` reads off every config: constants here, not
    # fields (no ring attention, no learned positions, the backend's dispatch)
    attn_impl = "flash"
    pos = "none"
    moe_dispatch = "auto"

    def __post_init__(self):
        bad = set(self.pattern) - set("M*ELDCSW")
        if bad or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: one of 'M', '*', 'E', 'L', 'D', "
                f"'C', 'S', 'W' a layer")
        if sum(bool(set(kinds) & set(self.pattern))
               for kinds in ("*CW", "L", "S")) > 1:
            raise ValueError(
                "one kind of block pool a model: '*' / 'C' (a 'W' layer's "
                "pool, of their block shape, may stand beside it), 'L' or "
                "'S'")
        if "W" in self.pattern and not (
                self.window > 0 and "*" in self.pattern):
            raise ValueError(
                "a 'W' layer needs window > 0 and a '*' layer beside it: "
                "the window layers' pool stands beside the pool of the "
                "layers that keep every position (a model of window layers "
                "alone is the GPTConfig family's sliding_window)")
        if not self.head_dim:
            if self.dim % self.nheads:
                raise ValueError("dim does not divide by nheads: say head_dim")
            object.__setattr__(self, "head_dim", self.dim // self.nheads)
        if (self.v_head_dim or self.window_kv_heads or self.rope_dims
                or self.global_rope_theta is not None):
            if set("CSL") & set(self.pattern):
                raise ValueError(
                    "v_head_dim, window_kv_heads, rope_dims and "
                    "global_rope_theta are the '*' / 'W' layers' alone")
            if (self.nheads % self.kv_heads or self.nheads % self.window_heads
                    or self.rope_dims % 2 or self.rope_dims > self.head_dim):
                raise ValueError(
                    "KV heads of either kind must divide nheads, and "
                    "rope_dims be even within a head")
        if "C" in self.pattern and (
                self.kv_heads % 2 or self.nheads % self.kv_heads
                or min(self.cca_time0, self.cca_time1) < 1
                or self.cca_rope % 2 or self.cca_rope > self.head_dim):
            raise ValueError(
                "a 'C' layer needs an even number of KV heads (half of "
                "them shifted) that divides nheads, kernels of at least "
                "1, and an even cca_rope within a head")
        if "S" in self.pattern:
            if not (self.idx_heads and self.idx_dim and self.idx_topk > 0
                    and self.kv_heads and self.nheads % self.kv_heads == 0):
                raise ValueError(
                    "an 'S' layer needs idx_heads, idx_dim, idx_topk and "
                    "KV heads that divide nheads")
            if self.idx_rope % 2 or self.idx_rope > self.idx_dim:
                raise ValueError("an even idx_rope within an indexer head")
            if self.mrope_section is not None and (
                    len(self.mrope_section) != 3
                    or sum(self.mrope_section) != self.head_dim // 2):
                raise ValueError(
                    f"mrope_section {self.mrope_section} must deal a head's "
                    f"{self.head_dim // 2} frequency pairs to three axes")
        if "L" in self.pattern and not (
                self.mla_latent and self.mla_nope and self.mla_rope
                and self.mla_v):
            raise ValueError("an 'L' layer needs the four mla_* widths")
        if "D" in self.pattern and not self.dense_ffn:
            raise ValueError("a 'D' layer needs dense_ffn")
        if "M" in self.pattern and not (
                self.mamba_heads and self.mamba_head_dim and self.ssm_state):
            raise ValueError("an 'M' layer needs the Mamba-2 sizes")
        if self.mamba_heads % self.ssm_groups:
            raise ValueError("mamba_heads must divide by ssm_groups")
        if "E" in self.pattern and not self.moe_experts:
            raise ValueError("an 'E' layer needs moe_experts")
        if self.moe_score == "mlp" and not self.moe_router_hidden:
            raise ValueError("the 'mlp' router needs moe_router_hidden")

    # ---- layer counts: the engine sizes its pool and its state by these
    @property
    def nlayers(self) -> int:
        return len(self.pattern)

    @property
    def kv_layers(self) -> int:
        """Layers that keep keys and values of EVERY position, or the
        latent they are made from (the block pool's depth)."""
        return sum(self.pattern.count(k) for k in "*LCS")

    @property
    def window_layers(self) -> int:
        """Layers that keep the last :attr:`window` positions' keys and
        values alone (the window pool's depth; 0: no such pool)."""
        return self.pattern.count("W")

    @property
    def value_width(self) -> int:
        """A value head's width in the '*' / 'W' layers."""
        return self.v_head_dim or self.head_dim

    @property
    def window_heads(self) -> int:
        """The 'W' layers' KV heads."""
        return self.window_kv_heads or self.kv_heads

    @property
    def latent_width(self) -> int:
        """What one position caches in an 'L' layer (0: a K/V pool)."""
        return (self.mla_latent + self.mla_rope) if "L" in self.pattern else 0

    @property
    def index_width(self) -> int:
        """What one position caches for the indexer of an 'S' layer beside
        its keys and values (0: no such leaf in the pool)."""
        return self.idx_dim if "S" in self.pattern else 0

    @property
    def kv_pack(self) -> int:
        """KV heads that share one row of the pool (1: a head a row).  A
        TPU tiles a bfloat16 array's minor dimension by 128 lanes: a pool
        whose rows are one head of 64 is held at twice its bytes, Mosaic
        cannot slice it, and XLA copies it whole for every call that does
        (PERF.md section 6, PR 46: compiled for a described v5e).  So heads
        narrower than 128 that fill a row exactly, ``kv_heads`` of them in
        whole rows, lie ``128 / head_dim`` to a row, and the attention ops
        reach each through zeroed lanes of the query
        (serving/paged_cache.py "Narrow heads").  Indexed and latent
        attention read their pools with kernels of their own: 1."""
        hd = self.head_dim
        if (set("SL") & set(self.pattern) or not 0 < hd < 128 or 128 % hd
                or self.kv_heads % (128 // hd) or self.value_width != hd
                or self.window_heads != self.kv_heads):
            return 1
        return 128 // hd

    @property
    def mla_scale(self) -> float:
        """The softmax scale of latent attention: the query head's width,
        times yarn's ``mscale`` squared where the rope is stretched (the
        cos/sin tables themselves stay unscaled when ``mscale ==
        mscale_all_dim``, which ``rope_cache`` works out)."""
        scale = (self.mla_nope + self.mla_rope) ** -0.5
        rs = self.rope_scaling or {}
        if rs.get("mscale_all_dim") and float(rs.get("factor", 1.0)) > 1.0:
            m = 0.1 * float(rs["mscale_all_dim"]) * math.log(
                float(rs["factor"])) + 1.0
            scale *= m * m
        return scale

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a recurrent state instead."""
        return self.pattern.count("M")

    @property
    def state_layers(self) -> int:
        """Layers that carry something from one position to the next
        beside the pool (:meth:`state_shapes`): a request's cached blocks
        alone do not say where such a layer stands."""
        return self.pattern.count("M") + self.pattern.count("C")

    @property
    def cca_channels(self) -> int:
        """The convolutions' channels: every query and key head."""
        return (self.nheads + self.kv_heads) * self.head_dim

    @property
    def cca_tail(self) -> int:
        """What a 'C' layer keeps of a sequence: the ``time0 + time1 - 2``
        rows of ``[q~ ; k~]`` before the next position (as projected, NOT
        convolved: zero rows are then the start of a sequence in every
        layer) and the last position's shifted value heads."""
        return ((self.cca_time0 + self.cca_time1 - 2) * self.cca_channels
                + self.kv_heads // 2 * self.head_dim)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def block(self) -> TransformerConfig:
        """The attention layers' shape, as the pool and the paged ops read
        it (head counts and head size; nothing positional: a 'C' layer
        rotates its own keys before the write).  Its ``dim`` is the
        attention's width, ``nheads x head_dim``, not the model's."""
        return TransformerConfig(
            dim=self.nheads * self.head_dim, nheads=self.nheads,
            nlayers=max(self.kv_layers, 1), kv_heads=self.kv_heads,
            dtype=self.dtype, norm="rms", norm_eps=self.norm_eps, rope=False)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(
            dim=self.dim, ffn_dim=self.moe_ffn, num_experts=self.moe_experts,
            top_k=self.moe_top_k, dtype=self.dtype, act=self.moe_act,
            score=self.moe_score, routed_scale=self.moe_routed_scale,
            latent_dim=self.moe_latent, shared_ffn=self.moe_shared_ffn,
            held=self.moe_held, dispatch=self.moe_dispatch,
            norm_eps=self.norm_eps)

    def state_shapes(
        self, rows: int,
    ) -> Dict[str, Tuple[Tuple[int, ...], Any, int]]:
        """What the layers keep of ``rows`` sequences beside the pool: name
        -> (one layer's shape, dtype, how many layers keep one).  A Mamba
        layer: its recurrent state and its convolution's rows; a 'C' layer:
        its tail (:attr:`cca_tail`), flat, so that a row is whole lanes."""
        m, c = self.pattern.count("M"), self.pattern.count("C")
        return {
            "ssm": ((rows, self.mamba_heads, self.mamba_head_dim,
                     self.ssm_state), self.state_dtype, m),
            "conv": ((rows, self.conv_kernel - 1, self.conv_channels),
                     self.dtype, m),
            "tail": ((rows, self.cca_tail), self.dtype, c),
        }

    def state_bytes(self, rows: int) -> int:
        return sum(math.prod(shape) * jnp.dtype(dt).itemsize * layers
                   for shape, dt, layers in self.state_shapes(rows).values())


def init_state(cfg: HybridConfig, rows: int) -> Dict[str, Tuple[jnp.ndarray, ...]]:
    """Zeroed state for ``rows`` sequences: ``{'ssm': (one [rows, H, P, N]
    array a Mamba layer), 'conv': (one [rows, K-1, C] a Mamba layer),
    'tail': (one [rows, cca_tail] a 'C' layer)}``.  One array a layer and
    not a stacked ``[L, ...]`` one: a step that is handed them as donated
    buffers then updates each in place (a stacked array would be rebuilt by
    a concatenate, and held twice)."""
    return {name: tuple(jnp.zeros(shape, dt) for _ in range(layers))
            for name, (shape, dt, layers) in cfg.state_shapes(rows).items()}


# ------------------------------------------------------------------ Mamba-2


def _ssd_chunk(x, dt, A, Bm, Cm, S0):
    """One chunk of the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t`` in its matrix form (Dao & Gu 2024, "SSD").
    Grouped layout, heads = G groups x R heads each:

    x [b, Q, G, R, P], dt [b, Q, G, R] (0 where the position is padding:
    decay 1 and no input, so the state passes through), A [G, R] (< 0),
    Bm / Cm [b, Q, G, N], S0 [b, G, R, P, N].  All float32.  Returns
    (y [b, Q, G, R, P], S1)."""
    Q = x.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)                      # [b, Q, G, R], <= 0
    t = jnp.arange(Q)
    tri = (t[:, None] >= t[None, :])[None, :, :, None, None]
    # decay from source s to target t (s <= t): exp(cum_t - cum_s) <= 1
    L = jnp.exp(jnp.where(tri, cum[:, :, None] - cum[:, None, :], -jnp.inf))
    CB = jnp.einsum("btgn,bsgn->btsg", Cm, Bm, precision=_HI)
    M = L * CB[..., None] * dt[:, None]                   # [b, t, s, G, R]
    y = jnp.einsum("btsgr,bsgrp->btgrp", M, x, precision=_HI)
    # what the carried state adds: exp(cum_t) S0 C_t
    y = y + jnp.einsum("btgn,bgrpn->btgrp", Cm, S0,
                       precision=_HI) * jnp.exp(cum)[..., None]
    to_end = jnp.exp(cum[:, -1:] - cum) * dt              # [b, Q, G, R]
    S1 = (jnp.exp(cum[:, -1])[..., None, None] * S0
          + jnp.einsum("bsgr,bsgrp,bsgn->bgrpn", to_end, x, Bm,
                       precision=_HI))
    return y, S1


def _ssd_step(x, dt, A, Bm, Cm, S0):
    """The one-step form, for a single position: x [b, G, R, P], dt
    [b, G, R], Bm / Cm [b, G, N], S0 [b, G, R, P, N]."""
    S1 = (jnp.exp(dt * A)[..., None, None] * S0
          + (dt[..., None] * x)[..., None] * Bm[:, :, None, None, :])
    y = jnp.sum(S1 * Cm[:, :, None, None, :], axis=-1)
    return y, S1


@prof.scoped(prof.MIXER)
def mamba2_mixer(
    p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: HybridConfig,
    ssm: jnp.ndarray, conv: jnp.ndarray, n_valid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] (already normed) -> (y [B, S, D], ssm, conv).

    ``ssm`` [B, H, P, N] and ``conv`` [B, K-1, C] are each row's state
    BEFORE this call's positions; ``n_valid`` [B] says how many of the S
    positions are real.  The rest is padding (a prompt's last chunk, a
    slot the decode call masks): it advances neither the state nor the
    convolution's rows, so a row with ``n_valid == 0`` gets its state back
    bit for bit.  ``S == 1`` is the one-step recurrence; longer calls run
    the chunked form, ``cfg.ssm_chunk`` positions a chunk, carrying the
    state from chunk to chunk."""
    B, S, _ = x.shape
    H, P, N, G = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    R, K, di = H // G, cfg.conv_kernel, cfg.d_inner
    valid = jnp.arange(S)[None, :] < n_valid[:, None]     # [B, S]

    zxbcdt = dense(x, p["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cfg.conv_channels],
                  zxbcdt[..., di + cfg.conv_channels:])

    with jax.named_scope(prof.SCAN):
        # depthwise causal convolution over (x, B, C): position t sees the
        # K-1 rows before it, the first of them from the carried tail
        cat = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)
        w = p["conv_w"].astype(F32)
        acc = p["conv_b"].astype(F32) + sum(
            cat[:, k:k + S].astype(F32) * w[k] for k in range(K))
        xbc_c = jax.nn.silu(acc)                          # float32 [B, S, C]
        # the tail after this call: the K-1 rows that end at the last REAL one
        conv = jax.vmap(
            lambda c, n: jax.lax.dynamic_slice_in_dim(c, n, K - 1, axis=0)
        )(cat, n_valid).astype(conv.dtype)

        xs = xbc_c[..., :di].reshape(B, S, G, R, P)
        Bm = xbc_c[..., di:di + G * N].reshape(B, S, G, N)
        Cm = xbc_c[..., di + G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
        dt = jnp.where(valid[..., None], dt, 0.0).reshape(B, S, G, R)
        A = -jnp.exp(p["A_log"].astype(F32)).reshape(G, R)
        S0 = ssm.astype(F32).reshape(B, G, R, P, N)

        if S == 1:
            y, S1 = _ssd_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], S0)
            y = y[:, None]
        else:
            Q = min(cfg.ssm_chunk, S)
            if S % Q:
                raise ValueError(
                    f"{S} positions do not divide into chunks of {Q}")

            def chunks(a):   # [B, S, ...] -> [S/Q, B, Q, ...]
                return jnp.moveaxis(
                    a.reshape((B, S // Q, Q) + a.shape[2:]), 1, 0)

            def body(Sc, c):
                yc, Sc = _ssd_chunk(*c[:2], A, *c[2:], Sc)
                return Sc, yc

            S1, ys = jax.lax.scan(
                body, S0, (chunks(xs), chunks(dt), chunks(Bm), chunks(Cm)))
            y = jnp.moveaxis(ys, 0, 1).reshape(B, S, G, R, P)
        y = y + p["D"].astype(F32).reshape(G, R)[..., None] * xs
        ssm = S1.reshape(B, H, P, N).astype(ssm.dtype)

    # gate, then RMSNorm within each of the G groups of d_inner / G
    y = y.reshape(B, S, di) * jax.nn.silu(z.astype(F32))
    yg = y.reshape(B, S, G, di // G)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (yg.reshape(B, S, di)
         * p["gate_norm"]["scale"].astype(F32)).astype(x.dtype)
    return dense(y, p["out_proj"]), ssm, conv


# ---------------------------------------------------------------- attention


@prof.scoped(prof.MIXER)
def attention_mixer(p, x, cfg: HybridConfig, ck, cv, offset, cache_ops,
                    window: Optional[int] = None):
    """GQA on a block pool: x [B, S, D] (normed) -> (y, ck, cv).  ``window``
    None: a ``*`` layer, every key ``<= t``, position-free unless the model
    states ``global_rope_theta``; else a ``W`` layer: queries and keys
    rotated by ``rope_theta``, keys in ``(t - window, t]``.  A rotation
    turns the whole head, or its leading ``rope_dims`` (half-split pairs
    within them, the rest as it lies).  Leaves ``q_norm`` / ``k_norm``: a
    learned RMSNorm over each query and key head (before the rotation);
    ``wg``: an output gate, ``y = (o * sigmoid(x W_g)) W_o``; ``wqkv`` in
    place of ``wq`` and ``wkv``: the three projections side by side, ``[q
    | k | v]``, the one layout that holds value heads of another width than
    the key heads (``v_head_dim``; the KV heads are counted from the leaf);
    ``sink`` [nheads] float32: one more column of every row's softmax, which
    gives no value.  The value states are multiplied by ``value_scale`` in
    float32 and rounded once, BEFORE they are cached: folded into ``W_v`` or
    ``W_o`` it would round a weight the reference does not round.
    ``cache_ops`` is the ``(write, attend)`` pair of
    ``serving/paged_cache.py`` for the layer's own pool, as
    ``cached_block_forward`` takes it."""
    B, S, _ = x.shape
    hd, hv = cfg.head_dim, cfg.value_width
    write, attend = cache_ops
    if "wqkv" in p:
        qkv = dense(x, p["wqkv"])
        dq = cfg.nheads * hd
        dk = (qkv.shape[-1] - dq) // (hd + hv) * hd
        q = qkv[..., :dq].reshape(B, S, -1, hd)
        k = qkv[..., dq:dq + dk].reshape(B, S, -1, hd)
        v = qkv[..., dq + dk:].reshape(B, S, -1, hv)
    else:
        q = dense(x, p["wq"]).reshape(B, S, -1, hd)
        kv = dense(x, p["wkv"], "bsd,tdh->tbsh")
        # v is taken out behind the transposes below, where it always was:
        # the other models' traces keep their order of operations
        k, v = kv[0].reshape(B, S, -1, hd), None
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    if v is None:
        v = kv[1].reshape(B, S, -1, hd)
    v = v.transpose(0, 2, 1, 3)
    if cfg.value_scale != 1.0:
        v = (v.astype(F32) * cfg.value_scale).astype(v.dtype)
    theta = cfg.rope_theta if window is not None else cfg.global_rope_theta
    if theta is not None:
        rd = cfg.rope_dims or hd
        pos = offset[:, None] + jnp.arange(S)[None, :]
        cos, sin = rope_cache(pos.reshape(-1), rd, theta,
                              scaling=cfg.rope_scaling)
        rope = (cos.reshape(B, 1, S, -1), sin.reshape(B, 1, S, -1))
        if rd == hd:
            q, k = apply_rope(q, cache=rope), apply_rope(k, cache=rope)
        else:
            q, k = (jnp.concatenate(
                [apply_rope(a[..., :rd], cache=rope), a[..., rd:]], axis=-1)
                    for a in (q, k))
    ck = write(ck, k, offset)
    cv = write(cv, v, offset)
    sink = {"sink": p["sink"]} if "sink" in p else {}
    out = attend(q, ck, cv, offset, window=window, **sink)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, q.shape[1] * hv)
    if "wg" in p:
        out = out * jax.nn.sigmoid(dense(x, p["wg"]).astype(F32)).astype(
            out.dtype)
    return dense(out, p["wo"]), ck, cv


@prof.scoped(prof.MIXER)
def cca_mixer(p, x, cfg: HybridConfig, ck, cv, tail, offset, n_valid,
              cache_ops):
    """Compressed convolutional attention (arXiv:2510.04476) on the block
    pool: x [B, S, D] (normed) -> (y, ck, cv, tail).

    ``z = x W_z`` is ``[q~ ; k~]``, ``nheads + kv_heads`` heads of
    ``head_dim``: attention runs in this latent.  Two causal convolutions
    over the sequence mix it: ``c0`` depthwise (``conv0_w`` [K0, C]), then
    ``c1`` grouped, one group a head (``conv1_w`` [K1, heads, hd, hd]), the
    PAIR padded once on the left by ``K0 + K1 - 2`` zero rows of ``z`` (so
    ``c0`` before the first position is its bias, not zero).  The q-k mean
    ``m = (q~ + k~) / 2`` (a query head with its key head) is added back,
    ``q = c1_q + m``, ``k = c1_k + mean over its query heads of m``; each
    head is scaled to length ``sqrt(head_dim)``, the key times ``k_temp``
    a KV head; rope turns the leading ``cca_rope`` dims.  The first half of
    the value heads are this position's ``x W_v``, the second half the
    position BEFORE's (zero before the first).

    ``tail`` [B, cca_tail] is what each row's sequence left behind BEFORE
    this call: the ``K0 + K1 - 2`` rows of ``z`` before its first position,
    then the last position's shifted value heads; ``n_valid`` [B] says how
    many of the S positions are real.  The tail that comes back ends at the
    last REAL one (``n_valid == 0``: bit for bit what came in), so prefill
    in chunks and decode give the numbers of one unchunked call.
    ``cache_ops``: the K/V pool's ``(write, attend)`` pair, as
    :func:`attention_mixer` takes it."""
    B, S, _ = x.shape
    H, Hkv, hd, C = cfg.nheads, cfg.kv_heads, cfg.head_dim, cfg.cca_channels
    R, K0, K1 = H // Hkv, cfg.cca_time0, cfg.cca_time1
    T, shifted = K0 + K1 - 2, Hkv // 2 * hd
    write, attend = cache_ops

    z = dense(x, p["wz"])                                  # [B, S, C]
    cat = jnp.concatenate(
        [tail[:, :T * C].reshape(B, T, C).astype(z.dtype), z], axis=1)
    # c0 at the K1 - 1 positions before the call's first, then at its own
    w0, n0 = p["conv0_w"].astype(F32), S + K1 - 1
    c0 = p["conv0_b"].astype(F32) + sum(
        cat[:, j:j + n0].astype(F32) * w0[j] for j in range(K0))
    # float32 operands: the MXU's default pass rounds them to bfloat16 and
    # accumulates in float32, and the CPU has no bf16 x bf16 -> f32 product
    c0 = c0.reshape(B, n0, H + Hkv, hd)
    c1 = p["conv1_b"].astype(F32).reshape(H + Hkv, hd) + sum(
        jnp.einsum("bsgd,gde->bsge", c0[:, j:j + S],
                   p["conv1_w"][j].astype(F32)) for j in range(K1))

    zf = z.astype(F32)
    m = 0.5 * (zf[..., :H * hd].reshape(B, S, Hkv, R, hd)
               + zf[..., H * hd:].reshape(B, S, Hkv, 1, hd))
    q = c1[:, :, :H] + m.reshape(B, S, H, hd)
    k = c1[:, :, H:] + jnp.mean(m, axis=3)

    def unit(a):   # length sqrt(hd): unit mean square
        return a * jax.lax.rsqrt(
            jnp.mean(a * a, axis=-1, keepdims=True) + 1e-12)

    k = unit(k) * p["k_temp"].astype(F32)[:, None]
    pos = offset[:, None] + jnp.arange(S)[None, :]
    cos, sin = rope_cache(pos.reshape(-1), cfg.cca_rope, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    rope = (cos.reshape(B, 1, S, -1), sin.reshape(B, 1, S, -1))

    def turned(a):   # [B, S, heads, hd] -> [B, heads, S, hd], rope applied
        a = a.transpose(0, 2, 1, 3)
        return jnp.concatenate(
            [apply_rope(a[..., :cfg.cca_rope], cache=rope),
             a[..., cfg.cca_rope:]], axis=-1).astype(x.dtype)

    q, k = turned(unit(q)), turned(k)

    v = dense(x, p["wv"])                                  # [B, S, Hkv * hd]
    vcat = jnp.concatenate(
        [tail[:, None, T * C:].astype(v.dtype), v[..., Hkv * hd - shifted:]],
        axis=1)                                            # [B, 1 + S, shifted]
    v = jnp.concatenate([v[..., :Hkv * hd - shifted], vcat[:, :S]], axis=-1)
    v = v.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)

    # the tail after this call: what lies before position n_valid
    def ends_at(c, n, rows):
        return jax.lax.dynamic_slice_in_dim(c, n, rows, axis=0).reshape(-1)

    with jax.named_scope(prof.KV_WRITE):
        tail = jnp.concatenate(
            [jax.vmap(lambda c, n: ends_at(c, n, T))(cat, n_valid),
             jax.vmap(lambda c, n: ends_at(c, n, 1))(vcat, n_valid)],
            axis=-1).astype(tail.dtype)

    ck = write(ck, k, offset)
    cv = write(cv, v, offset)
    out = attend(q, ck, cv, offset, window=None)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return dense(out, p["wo"]), ck, cv, tail


def mrope_cache(positions: jnp.ndarray, head_dim: int, theta: float,
                section: Optional[Tuple[int, ...]]):
    """(cos, sin) [B, 1, S, head_dim / 2] for :func:`apply_rope` from THREE
    position rows ``positions`` [3, B, S] (temporal, height, width): of a
    head's frequency pairs (half-split, ``(i, i + head_dim / 2)``) the first
    ``section[0]`` turn by the temporal position, the next ``section[1]`` by
    the height, the last ``section[2]`` by the width.  A text token has the
    three equal, and this is then :func:`rope_cache` of that position, bit
    for bit.  ``section`` None: the temporal row alone."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    axis_of = np.repeat(np.arange(3), (half, 0, 0) if section is None
                        else section)
    pos = jnp.take(positions.astype(F32), axis_of, axis=0)    # [half, B, S]
    ang = jnp.moveaxis(pos, 0, -1) * inv_freq                 # [B, S, half]
    return jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]


@prof.scoped(prof.MIXER)
def indexed_attention_mixer(p, x, cfg: HybridConfig, cache, offset,
                            cache_ops, positions=None):
    """Indexed (sparse) attention on the block pool: x [B, S, D] (normed)
    -> (y, cache, selection), ``cache`` the pool's three leaves ``{'k', 'v',
    'idx'}``, ``selection`` [B, S, words] int16 the positions each row kept,
    as bits (``ops.dsa_attention.selection_words``).

    Attention is grouped-query attention with a learned RMSNorm over each
    query head and each key head before the rotation (:func:`mrope_cache`).
    The INDEXER decides which cached positions a query reads: ``qI = x
    W_qI`` (``idx_heads`` heads of ``idx_dim``), ONE key a position ``kI =
    LayerNorm(x W_kI)`` (cached in the pool's ``idx`` leaf), both rotated on
    their leading ``idx_rope`` dims by the temporal position, head weights
    ``w = (x W_w) idx_heads^-0.5 idx_dim^-0.5`` in float32, and the score of
    position s for the query at t (s <= t) is ``sum_j w_j relu(qI_j .
    kI_s)``.  The ``min(idx_topk, t + 1)`` positions of largest score (equal
    scores: the lower position first) are the ones ALL query heads of t
    attend to; the rest are never read as keys or values.  This call's own
    rows are written first (keys, values and indexer keys alike), so a
    position may select itself and a chunk's rows select among the chunk's.

    ``positions`` [3, B, S]: the three position rows; None: a text token's,
    ``offset[b] + arange(S)`` three times.  ``cache_ops``: ``(write,
    write_idx, attend)`` of ``serving/paged_cache._indexed_cache_ops``."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg.nheads, cfg.kv_heads, cfg.head_dim
    J, di, dr = cfg.idx_heads, cfg.idx_dim, cfg.idx_rope
    write, write_idx, attend = cache_ops
    if positions is None:
        positions = jnp.broadcast_to(
            offset[:, None] + jnp.arange(S)[None, :], (3, B, S))
    rope = mrope_cache(positions, hd, cfg.rope_theta, cfg.mrope_section)

    q = rms_norm(dense(x, p["wq"]).reshape(B, S, H, hd), p["q_norm"],
                 cfg.norm_eps)
    kv = dense(x, p["wkv"], "bsd,tdh->tbsh")
    k = rms_norm(kv[0].reshape(B, S, Hkv, hd), p["k_norm"], cfg.norm_eps)
    q = apply_rope(q.transpose(0, 2, 1, 3), cache=rope)
    k = apply_rope(k.transpose(0, 2, 1, 3), cache=rope)
    v = kv[1].reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)

    # the indexer's query and key stay in float32 from the projection
    # through the norm and the rotation and are rounded ONCE, as they are
    # cached and multiplied: a score's rounding error decides which
    # positions sit on which side of the ``idx_topk``-th, and every
    # intermediate rounding adds to it
    irope = mrope_cache(positions, dr, cfg.rope_theta, None)

    def turned(a):   # [B, heads, S, di] float32: rope on the leading ``dr``
        return jnp.concatenate(
            [apply_rope(a[..., :dr], cache=irope), a[..., dr:]],
            axis=-1).astype(x.dtype)

    def proj(w):
        return jnp.einsum("bsd,dn->bsn", x, w, preferred_element_type=F32)

    qi = turned(proj(p["wq_idx"]).reshape(B, S, J, di).transpose(
        0, 2, 1, 3))                                       # [B, J, S, di]
    ki = turned(layer_norm(proj(p["wk_idx"]), p["k_idx_norm"],
                           cfg.norm_eps)[:, None])[:, 0]   # [B, S, di]
    w = proj(p["w_idx"]) * (J ** -0.5 * di ** -0.5)

    cache = dict(cache)
    cache["k"] = write(cache["k"], k, offset)
    cache["v"] = write(cache["v"], v, offset)
    cache["idx"] = write_idx(cache["idx"], ki, offset)
    out, kept = attend(q, cache["k"], cache["v"], cache["idx"], qi, w, offset)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return dense(out, p["wo"]), cache, kept


@prof.scoped(prof.MIXER)
def latent_attention_mixer(p, x, cfg: HybridConfig, pool, offset, cache_ops):
    """Latent attention in the absorbed form: x [B, S, D] (normed) -> (y,
    pool).  A position caches ONE row, ``[RMSNorm(c) | rope(k_rope)]``
    (``mla_latent + mla_rope`` wide), which is every head's key AND, in its
    first ``mla_latent`` columns, every head's value: head h's query goes
    into the latent through ``wuk[h]`` (its key up-projection, transposed),
    the heads attend to the shared rows, and what comes out of the latent
    goes through ``wuv[h]`` to the head's value width.  The keys and values
    of the published form, ``wuk[h] c`` and ``wuv[h] c``, are never made.
    Each query head is normed (a learned RMSNorm over its whole width)
    before its rope part is rotated; ``k_rope`` is not normed.
    ``cache_ops``: ``(write, attend)`` of ``serving/paged_cache.py`` for the
    latent pool."""
    B, S, _ = x.shape
    H, dn, dr, dc = cfg.nheads, cfg.mla_nope, cfg.mla_rope, cfg.mla_latent
    write, attend = cache_ops
    pos = offset[:, None] + jnp.arange(S)[None, :]
    cos, sin = rope_cache(pos.reshape(-1), dr, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    rope = (cos.reshape(B, 1, S, dr // 2), sin.reshape(B, 1, S, dr // 2))

    q = dense(x, p["wq"]).reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q_lat = jnp.einsum("bhsn,hnc->bhsc", q[..., :dn], p["wuk"])
    q = jnp.concatenate(
        [q_lat, apply_rope(q[..., dn:], cache=rope)], axis=-1)
    kva = dense(x, p["wkva"])                              # [B, S, dc + dr]
    row = jnp.concatenate(
        [rms_norm(kva[..., :dc], p["kv_norm"], cfg.norm_eps),
         apply_rope(kva[:, None, :, dc:], cache=rope)[:, 0]], axis=-1)
    pool = write(pool, row, offset)
    o_lat = attend(q, pool, offset)                        # [B, H, S, dc]
    o = jnp.einsum("bhsc,hcv->bshv", o_lat, p["wuv"])
    return dense(o.reshape(B, S, H * cfg.mla_v), p["wo"]), pool


# ------------------------------------------------------------------ forward


def hybrid_paged_forward(
    params: Dict[str, PyTree],
    tokens: jnp.ndarray,
    cfg: HybridConfig,
    cache: Dict[str, Any],
    state: Dict[str, Tuple[jnp.ndarray, ...]],
    n_valid: jnp.ndarray,
    cache_ops,
    offset: jnp.ndarray,
    last_idx=None,
    positions=None,
    window_ops=None,
):
    """``tokens`` [B, S] through the stack.  ``cache``: the block pool of
    the attention layers (``{'k','v': [kv_layers, ...]}``, with an ``'idx'``
    leaf beside them where attention is indexed, or ``{'kv': ...}`` where
    it is latent), reached through
    ``cache_ops(layer)`` (the pool's ``(write, attend)`` pair for one of
    its layers; the pool itself is threaded whole through the attention
    layers).  A model with 'W' layers has a second pool under
    ``cache['win']`` (``{'k','v': [window_layers, ...]}``), reached through
    ``window_ops(layer)``, that pool's pair over its own table; a layer of
    either pool is named by its index WITHIN its kind.  ``state``: :func:`init_state`'s arrays with one row a
    row of ``tokens``; ``n_valid`` [B]: the real positions of each row.
    The network router's stream (``moe_score='mlp'``) is a second carry
    through the loop, from one expert layer to the next.  ``positions``
    [3, B, S]: an 'S' layer's three position rows (None: text positions,
    ``offset[b] + arange(S)``; the engine serves token ids and passes none).
    Returns ``(cache, state, logits [B, V], moe_metrics)``: the logits of
    row ``last_idx`` (default: the last), and the expert layers' counters
    summed over the layers, with ``routing`` [B, S, E-layers, k]: the
    experts every position chose in every expert layer (None without an
    'E' layer), and where attention is indexed ``selection`` [B, S,
    S-layers, words] int16: the positions every row kept in every 'S' layer,
    as bits (``ops.dsa_attention.selection_words``)."""
    from ..serving.paged_cache import _select_row

    S = tokens.shape[1]
    valid = jnp.arange(S)[None, :] < n_valid[:, None]
    with jax.named_scope(prof.EMBED):
        h = jnp.take(params["tok_emb"], tokens, axis=0)
        if cfg.embed_scale != 1.0:
            h = (h.astype(F32) * cfg.embed_scale).astype(h.dtype)
    cache, kv_layer = dict(cache), 0
    win = dict(cache["win"]) if "win" in cache else None
    w_layer = 0
    ssm, conv, tails, mets, kept = [], [], [], [], []
    mcfg = cfg.moe if cfg.moe_experts else None
    depth = None
    for kind, lp in zip(cfg.pattern, params["layers"]):
        # a block half, from its norm to the residual add behind it
        with jax.named_scope(prof.FFN if kind in "DE" else prof.MIXER):
            x = rms_norm(h, lp["norm"], cfg.norm_eps)
            if kind == "M":
                m = len(ssm)
                y, s_m, c_m = mamba2_mixer(
                    lp, x, cfg, state["ssm"][m], state["conv"][m], n_valid)
                ssm.append(s_m)
                conv.append(c_m)
            elif kind == "*":
                y, cache["k"], cache["v"] = attention_mixer(
                    lp, x, cfg, cache["k"], cache["v"], offset,
                    cache_ops(kv_layer))
                kv_layer += 1
            elif kind == "W":
                y, win["k"], win["v"] = attention_mixer(
                    lp, x, cfg, win["k"], win["v"], offset,
                    window_ops(w_layer), window=cfg.window)
                w_layer += 1
            elif kind == "C":
                y, cache["k"], cache["v"], tail = cca_mixer(
                    lp, x, cfg, cache["k"], cache["v"],
                    state["tail"][len(tails)], offset, n_valid,
                    cache_ops(kv_layer))
                tails.append(tail)
                kv_layer += 1
            elif kind == "S":
                y, cache, rows_kept = indexed_attention_mixer(
                    lp, x, cfg, cache, offset, cache_ops(kv_layer),
                    positions=positions)
                kept.append(rows_kept)
                kv_layer += 1
            elif kind == "L":
                y, cache["kv"] = latent_attention_mixer(
                    lp, x, cfg, cache["kv"], offset, cache_ops(kv_layer))
                kv_layer += 1
            elif kind == "D":
                y = dense(_unbiased_act(dense(x, lp["w1"]), "swiglu"),
                          lp["w2"])
            else:
                y, met, *stream = moe_serve_forward(
                    lp, x, mcfg, return_metrics=True, valid=valid,
                    depth=depth)
                mets.append(met)
                depth = stream[0] if stream else None
            if "post_norm" in lp:
                y = rms_norm(y, lp["post_norm"], cfg.norm_eps)
            if "res" in lp:
                a_h, b_h, a_y, b_y = (lp["res"][k].astype(F32) for k in (
                    "a_h", "b_h", "a_y", "b_y"))
                h = (a_h * h.astype(F32) + b_h + a_y * y.astype(F32)
                     + b_y).astype(h.dtype)
            elif cfg.residual_scale != 1.0:
                h = (h.astype(F32)
                     + cfg.residual_scale * y.astype(F32)).astype(h.dtype)
            else:
                h = h + y
    state = {"ssm": tuple(ssm), "conv": tuple(conv), "tail": tuple(tails)}
    if win is not None:
        cache["win"] = win
    metrics = None
    if mets:
        routing = jnp.stack([m.pop("gate_idx") for m in mets], axis=2)
        metrics = {k: sum(m[k] for m in mets) for k in mets[0]}
        metrics["routing"] = routing
        if kept:
            metrics["selection"] = jnp.stack(kept, axis=2)
    with jax.named_scope(prof.HEAD):
        h = rms_norm(_select_row(h, last_idx), params["ln_f"], cfg.norm_eps)
        if "head" in params:
            logits = dense(h, params["head"])
        else:   # tied: the table as it lies, contracted over its rows' width
            logits = jnp.einsum("bsd,vd->bsv", h, params["tok_emb"])
        if cfg.logits_scale != 1.0:
            logits = (logits.astype(F32) * cfg.logits_scale).astype(
                logits.dtype)
    return cache, state, logits[:, 0, :], metrics


# --------------------------------------------------------------------- init


def init_hybrid_params(key, cfg: HybridConfig, scaled_residual: bool = False,
                       tied_head: bool = False) -> Dict[str, PyTree]:
    """Seeded parameters in the layout :func:`hybrid_paged_forward` reads
    (tests and examples; a checkpoint converter is not written yet).
    ``scaled_residual``: every layer gets the four ``res`` vectors (at the
    identity: a 1, b 0); ``tied_head``: no ``head`` leaf.  The optional
    leaves (``wg``, the head norms, ``post_norm``) are a weight file's."""
    dt, D = cfg.dtype, cfg.dim

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(fan_in)).astype(dt)

    def norm():
        return {"scale": jnp.ones((D,), dt)}

    layers: List[Dict[str, Any]] = []
    keys = jax.random.split(key, len(cfg.pattern) + 2)
    hd, first_expert_layer = cfg.head_dim, cfg.pattern.find("E")
    for i, (kind, k) in enumerate(zip(cfg.pattern, keys)):
        ks = jax.random.split(k, 8)
        if kind == "M":
            di, C, H = cfg.d_inner, cfg.conv_channels, cfg.mamba_heads
            lp = {
                "in_proj": normal(ks[0], (D, di + C + H), D),
                "conv_w": normal(ks[1], (cfg.conv_kernel, C), cfg.conv_kernel),
                "conv_b": jnp.zeros((C,), dt),
                # dt in [1e-3, 1e-1] through the softplus, A in [-16, -1]:
                # the published initialisation's ranges
                "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                    ks[2], (H,), F32, math.log(1e-3), math.log(1e-1))))),
                "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1., 16.)),
                "D": jnp.ones((H,), F32),
                "gate_norm": {"scale": jnp.ones((di,), dt)},
                "out_proj": normal(ks[4], (di, D), di),
            }
        elif kind in "*W":
            hkv = cfg.window_heads if kind == "W" else cfg.kv_heads
            dq, dkv, hv = cfg.nheads * hd, hkv * hd, cfg.value_width
            if hv != hd:   # the three projections side by side
                lp = {"wqkv": normal(ks[0], (D, dq + dkv + hkv * hv), D),
                      "wo": normal(ks[2], (cfg.nheads * hv, D),
                                   cfg.nheads * hv)}
            else:
                lp = {"wq": normal(ks[0], (D, dq), D),
                      "wkv": normal(ks[1], (2, D, dkv), D),
                      "wo": normal(ks[2], (dq, D), dq)}
        elif kind == "S":
            dq, dkv = cfg.nheads * hd, cfg.kv_heads * hd
            J, di = cfg.idx_heads, cfg.idx_dim
            lp = {"wq": normal(ks[0], (D, dq), D),
                  "wkv": normal(ks[1], (2, D, dkv), D),
                  "q_norm": {"scale": jnp.ones((hd,), dt)},
                  "k_norm": {"scale": jnp.ones((hd,), dt)},
                  "wo": normal(ks[2], (dq, D), dq),
                  "wq_idx": normal(ks[3], (D, J * di), D),
                  "wk_idx": normal(ks[4], (D, di), D),
                  "k_idx_norm": {"scale": jnp.ones((di,), dt),
                                 "bias": jnp.zeros((di,), dt)},
                  "w_idx": normal(ks[5], (D, J), D)}
        elif kind == "C":
            G, C = cfg.nheads + cfg.kv_heads, cfg.cca_channels
            lp = {"wz": normal(ks[0], (D, C), D),
                  "wv": normal(ks[1], (D, cfg.kv_heads * hd), D),
                  "conv0_w": normal(ks[2], (cfg.cca_time0, C), cfg.cca_time0),
                  "conv0_b": jnp.zeros((C,), dt),
                  "conv1_w": normal(ks[3], (cfg.cca_time1, G, hd, hd),
                                    cfg.cca_time1 * hd),
                  "conv1_b": jnp.zeros((C,), dt),
                  "k_temp": jnp.ones((cfg.kv_heads,), dt),
                  "wo": normal(ks[4], (cfg.nheads * hd, D), cfg.nheads * hd)}
        elif kind == "L":
            H, dn, dr, dc, dv = (cfg.nheads, cfg.mla_nope, cfg.mla_rope,
                                 cfg.mla_latent, cfg.mla_v)
            lp = {"wq": normal(ks[0], (D, H * (dn + dr)), D),
                  "q_norm": {"scale": jnp.ones((dn + dr,), dt)},
                  "wkva": normal(ks[1], (D, dc + dr), D),
                  "kv_norm": {"scale": jnp.ones((dc,), dt)},
                  "wuk": normal(ks[2], (H, dn, dc), dc),
                  "wuv": normal(ks[3], (H, dc, dv), dc),
                  "wo": normal(ks[4], (H * dv, D), H * dv)}
        elif kind == "D":
            lp = {"w1": normal(ks[0], (D, 2 * cfg.dense_ffn), D),
                  "w2": normal(ks[1], (cfg.dense_ffn, D), cfg.dense_ffn)}
        else:
            m = cfg.moe
            _, held = m.held_range
            lat = m.latent_dim or D
            # a gated expert's w1 is gate and up side by side
            wide = 2 if m.act == "swiglu" else 1
            if m.score != "mlp":
                router = {"w": normal(ks[0], (D, m.num_experts), D),
                          "bias": jnp.zeros((m.num_experts,), F32)}
            else:
                R = cfg.moe_router_hidden
                kr = jax.random.split(ks[0], 4)
                router = {
                    "down": {"w": normal(kr[0], (D, R), D),
                             "b": jnp.zeros((R,), dt)},
                    "norm": {"scale": jnp.ones((R,), dt)},
                    "w1": normal(kr[1], (R, R), R), "b1": jnp.zeros((R,), dt),
                    "w2": normal(kr[2], (R, R), R), "b2": jnp.zeros((R,), dt),
                    "w3": normal(kr[3], (R, m.num_experts), R),
                    "bias": jnp.zeros((m.num_experts,), F32)}
                if i != first_expert_layer:   # the first has no layer before
                    router["gamma"] = jnp.ones((R,), dt)
            lp = {"router": router,
                  "experts": {"w1": normal(
                      ks[1], (held, lat, wide * m.ffn_dim), lat),
                              "w2": normal(ks[2], (held, m.ffn_dim, lat),
                                           m.ffn_dim)}}
            if m.latent_dim:
                lp["latent"] = {"down": normal(ks[3], (D, lat), D),
                                "up": normal(ks[4], (lat, D), lat)}
            if m.shared_ffn:
                lp["shared"] = {"w1": normal(
                    ks[5], (D, wide * m.shared_ffn), D),
                                "w2": normal(ks[6], (m.shared_ffn, D),
                                             m.shared_ffn)}
        if scaled_residual:
            lp["res"] = {"a_h": jnp.ones((D,), dt), "b_h": jnp.zeros((D,), dt),
                         "a_y": jnp.ones((D,), dt), "b_y": jnp.zeros((D,), dt)}
        layers.append({"norm": norm(), **lp})
    out = {
        "tok_emb": (jax.random.normal(keys[-2], (cfg.vocab_size, D), F32)
                    * 0.02).astype(dt),
        "layers": layers,
        "ln_f": norm(),
    }
    if not tied_head:
        out["head"] = normal(keys[-1], (D, cfg.vocab_size), D)
    return out
