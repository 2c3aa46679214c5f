"""Operations and bytes of the served model, computed from shapes alone.

``matmul_shapes`` lists the analog matrix products of one layer as
(site, k, n). A fused-kernel call of ``m`` rows costs ``2 m k n`` operations,
whatever the number of noise repeats K (repeat noise is not matrix work),
and moves its operands and result once at bfloat16 size: ``x`` (m, k),
``w`` (k, n) and ``y`` (m, n). Per-token model work counts the layers'
matrix products, causal attention over the token's context, and the output
head once per token the head scores; the embedding gather is no
multiply-accumulate and is left out.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

BF16 = 2


def matmul_shapes(d: dict) -> List[Tuple[str, int, int]]:
    dm, hd = d["d_model"], d["head_dim"]
    qd, kd, ff = d["n_heads"] * hd, d["n_kv_heads"] * hd, d["d_ff"]
    out = [("q", dm, qd), ("k", dm, kd), ("v", dm, kd), ("o", qd, dm)]
    if d["mlp"] == "swiglu":
        out += [("gate", dm, ff), ("up", dm, ff), ("down", ff, dm)]
    else:
        out += [("in", dm, ff), ("down", ff, dm)]
    return out


def layer_matmul_params(d: dict) -> int:
    return sum(k * n for _, k, n in matmul_shapes(d))


def kernel_call_cost(m: int, k: int, n: int) -> Tuple[float, float]:
    """(operations, bytes) of one fused-kernel call of m rows."""
    return 2.0 * m * k * n, float(BF16 * (m * k + k * n + m * n))


def prefill_kernel_cost(d: dict, rows: int) -> Tuple[float, float]:
    """(operations, bytes) of every kernel call of one prefill dispatch of
    ``rows`` padded rows (batch bucket x seq bucket), over all layers."""
    flops = nbytes = 0.0
    for _, k, n in matmul_shapes(d):
        f, b = kernel_call_cost(rows, k, n)
        flops += f
        nbytes += b
    return flops * d["n_layers"], nbytes * d["n_layers"]


def attention_flops(d: dict, context: int) -> float:
    """Scores and weighted sum of one token over ``context`` positions."""
    return 4.0 * d["n_layers"] * d["n_heads"] * d["head_dim"] * context


def token_flops(d: dict, context: int, head: bool) -> float:
    """Model operations of one real token at position ``context - 1``."""
    f = 2.0 * d["n_layers"] * layer_matmul_params(d) + attention_flops(d, context)
    if head:
        f += 2.0 * d["d_model"] * d["vocab"]
    return f


def prompt_flops(d: dict, length: int) -> float:
    """A prefill of one real prompt: every token through the layers, causal
    attention, and the head once (for the first served token)."""
    f = 2.0 * d["n_layers"] * layer_matmul_params(d) * length
    f += attention_flops(d, 1) * length * (length + 1) / 2  # contexts 1..length
    return f + 2.0 * d["d_model"] * d["vocab"]


def decode_flops(d: dict, positions: Iterable[int]) -> float:
    """Decode steps of real rows, each at its own position."""
    return sum(token_flops(d, p + 1, head=True) for p in positions)
