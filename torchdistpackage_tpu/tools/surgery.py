"""Param-tree surgery + int8 weight-only quantization.

Analogue of ``module_replace.py`` (predicate-driven recursive module swap,
module_replace.py:1-7) and the int8 linear adapters ``bnb_fc.py`` /
``bminf_int8.py`` (swap ``nn.Linear`` for bitsandbytes/bminf CUDA int8
kernels).

TPU-native design: a JAX "module" is a param subtree + an apply function, so
*surgery is a pytree transform*: :func:`replace_params` rewrites leaves (or
whole subtrees) selected by a key-path predicate.  The int8 path needs no
external CUDA kernels — weights are stored int8 in HBM and upcast in-register
on the way into the MXU (weight-only quantization: compute stays bf16/fp32;
what int8 buys here is halved/quartered HBM weight traffic), and XLA fuses the
dequant scale into the matmul epilogue:

- :func:`quantize_int8` — symmetric per-output-channel weight quantization,
- :func:`int8_matmul` — activation stays bf16/fp32; weight upcast happens
  in-register on the way into the MXU, halving (vs bf16) or quartering
  (vs fp32) the HBM weight traffic, which is what int8 inference buys on a
  bandwidth-bound chip,
- :func:`quantize_params_int8` — one-call "replace every linear by its int8
  form" over a param tree (the ``replace_linear_by_bnb`` analogue).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.tree import key_str as _key_str

PyTree = Any


def replace_params(
    params: PyTree,
    predicate: Callable[[str, Any], bool],
    transform: Callable[[str, Any], Any],
) -> PyTree:
    """Rewrite every leaf whose ``(keypath, leaf)`` satisfies ``predicate``
    with ``transform(keypath, leaf)`` — the pytree analogue of
    ``replace_all_module`` (module_replace.py:1-7).  The transform may return
    a subtree (e.g. a :class:`QuantizedLinear`), not just an array.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        key = _key_str(path)
        out.append(transform(key, leaf) if predicate(key, leaf) else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedLinear:
    """int8 weight + per-output-channel fp scale, as a pytree leaf-pair.

    Stands in for a dense weight matrix; apply with :func:`int8_matmul`.
    Analogue of the bitsandbytes ``Linear8bitLt`` replacement (bnb_fc.py:10-23)
    with the kernel replaced by the MXU's native int8 path.
    """

    q: jax.Array      # (in, out) int8
    scale: jax.Array  # (out,) float

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def nbytes(self) -> int:
        return self.q.nbytes + self.scale.nbytes


def quantize_int8(w: jax.Array, scale_dtype=jnp.float32) -> QuantizedLinear:
    """Symmetric per-output-channel (last dim) int8 quantization."""
    absmax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = (absmax / 127.0 + 1e-12).astype(scale_dtype)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantizedLinear(q=q, scale=scale)


def dequantize_int8(ql: QuantizedLinear, dtype=jnp.float32) -> jax.Array:
    return ql.q.astype(dtype) * ql.scale.astype(dtype)


def int8_matmul(x: jax.Array, ql: QuantizedLinear) -> jax.Array:
    """``x @ dequant(qw)`` with the dequant fused into the matmul epilogue:
    the int8 weight is upcast to ``x.dtype`` in-register (halved HBM weight
    reads vs bf16) and the per-channel scale multiplies the product."""
    y = jnp.dot(x, ql.q.astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * ql.scale.astype(jnp.float32)).astype(x.dtype)


def quantize_stacked_int8(w: jax.Array, scale_dtype=jnp.float32) -> QuantizedLinear:
    """Symmetric int8 with per-(stack, output-channel) scales: absmax over
    the CONTRACTION dim (-2) only, keepdims, so a layer-stacked ``[L, ...,
    d, out]`` weight keeps one scale row per layer per channel — and both
    ``q`` and ``scale`` slice their leading dim through ``lax.scan``
    (QuantizedLinear is a pytree), which is what lets the decode scan carry
    int8 weights with the dequant INSIDE the scan body.  For a plain 2-D
    weight the scale is ``[1, out]`` (broadcast-equivalent to
    :func:`quantize_int8`'s ``[out]``)."""
    absmax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = (absmax / 127.0 + 1e-12).astype(scale_dtype)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantizedLinear(q=q, scale=scale)


#: weight leaf names of the GPT/Llama/MoE families that carry matmul
#: weights (attention projections, MLP/expert matrices, LM head) — the
#: decode-quantization sweep targets exactly these
DECODE_WEIGHT_KEYS = ("wqkv", "wq", "wkv", "wo", "w1", "w2", "head")


def quantize_decode_params(
    params: PyTree, min_size: int = 16384
) -> PyTree:
    """int8 weight-only quantization of a model param tree for SERVING.

    Replaces every matmul weight (:data:`DECODE_WEIGHT_KEYS`; stacked
    ``[L, ...]`` block leaves keep per-layer scales) with a
    :class:`QuantizedLinear`.  Embeddings, biases and norms stay dense —
    the win is HBM weight bandwidth on the matmuls, which is what bounds
    incremental decode (it reads every weight once per token).  The model functions dispatch structurally
    (``tensor_parallel.layers.dense``), so the quantized tree drops into
    ``models.generate``/``forward_cached`` unchanged — golden + jaxpr
    proof in tests/test_generate.py."""

    def pred(key: str, leaf: Any) -> bool:
        name = key.rsplit("/", 1)[-1]
        # MoE expert/router leaves reuse the w1/w2 names but run through the
        # expert einsums (parallel/moe.py), not the `dense` dispatch — they
        # stay dense until the expert paths learn the quantized layout
        if "experts" in key or "router" in key:
            return False
        return (
            name in DECODE_WEIGHT_KEYS
            and hasattr(leaf, "ndim")
            and leaf.ndim >= 2
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.size >= min_size
        )

    return replace_params(params, pred, lambda _k, w: quantize_stacked_int8(w))


def quantize_params_int8(
    params: PyTree,
    predicate: Optional[Callable[[str, Any], bool]] = None,
    min_size: int = 4096,
) -> PyTree:
    """Replace weight matrices with :class:`QuantizedLinear` leaves.

    Default predicate: floating 2-D leaves with at least ``min_size``
    elements (skips LN/bias/embedding-sized vectors) — the "all linears"
    sweep of ``replace_linear_by_bnb`` (bnb_fc.py:10-23).
    """

    def default_pred(key: str, leaf: Any) -> bool:
        return (
            hasattr(leaf, "ndim")
            and leaf.ndim == 2
            and hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.size >= min_size
        )

    pred = predicate or default_pred
    return replace_params(params, pred, lambda _k, w: quantize_int8(w))
