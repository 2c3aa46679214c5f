"""Int8 weight storage for serving (the decode-memory lever).

Decode is weight-streaming bound; storing matmul weights as int8 with
per-output-channel scales halves the parameter HBM traffic vs bf16. This is
paper-aligned: the thermal/weight-noise architectures already run 8-bit
digital I/O (Appendix A), so int8 weights change serving numerics no more
than the analog quantization the paper models.

``quantize_params`` converts an LM param tree (matmul leaves -> Int8Weight
with per-column scales; norms/biases/embeddings stay bf16);
``Int8DequantHook`` dequantizes at the matmul site.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Int8Weight:
    q: Array  # int8, same shape as the original weight
    scale: Array  # f32, per-output-channel (1, ..., M) broadcastable


def quantize_weight(w: Array) -> Int8Weight:
    """Symmetric per-output-channel int8: reduce over the contracting axis
    (-2) only, so stacked-layer leading dims survive (scan-sliceable) and
    every (layer, channel) pair gets its own scale."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return Int8Weight(q=q, scale=scale)


def dequantize_weight(iw: Int8Weight, dtype=jnp.bfloat16) -> Array:
    return (iw.q.astype(jnp.float32) * iw.scale).astype(dtype)


def _is_matmul_leaf(path: tuple, leaf: Array) -> bool:
    """Heuristic: >=2-D float leaves whose last-dim is an output channel.

    Embedding tables stay high precision (gather, not matmul); norms/biases
    are 1-D; conv/rope tables excluded by name.
    """
    name = "/".join(str(getattr(p, "key", p)) for p in path)
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    if "embed" in name or "norm" in name or name.endswith("ln"):
        return False
    # layer-stacked matmul weights are >=3-D (L, ..., K, M); 2-D stacked
    # leaves are biases/gains. The only quantizable top-level 2-D leaf is
    # the LM head.
    return leaf.ndim >= 3 or name.endswith("lm_head")


def quantize_params(params: PyTree) -> PyTree:
    """bf16 param tree -> tree with Int8Weight matmul leaves.

    Each leaf quantizes under ``jit``: fused, so a layer-stacked weight
    never materializes as float32 (op-by-op it would need several f32
    copies of the leaf on the device at once)."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    quantize = jax.jit(quantize_weight)
    out = []
    for path, leaf in flat[0]:
        out.append(quantize(leaf) if _is_matmul_leaf(path, leaf) else leaf)
    return jax.tree_util.tree_unflatten(flat[1], out)


def dequantize_params(qparams: PyTree, dtype=jnp.bfloat16) -> PyTree:
    """Inverse map (whole-tree); serving paths instead dequantize per-site
    inside the jitted step so int8 is what streams from HBM."""
    return jax.tree.map(
        lambda l: dequantize_weight(l, dtype) if isinstance(l, Int8Weight) else l,
        qparams,
        is_leaf=lambda l: isinstance(l, Int8Weight),
    )


def param_bytes(params: PyTree) -> int:
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
