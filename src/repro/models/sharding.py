"""Logical-axis sharding: params and activations are annotated with logical
axis names; a rule table maps them onto physical mesh axes.

Physical meshes (launch/mesh.py):
  single-pod: ("data", "model") = (16, 16)
  multi-pod:  ("pod", "data", "model") = (2, 16, 16)

The default rules implement TP on "model" (heads / mlp / vocab / experts),
DP on ("pod","data") for batch, ZeRO-1 optimizer-state sharding on
("pod","data") stacked on top of the param's own TP sharding, and KV-cache
sequence sharding on "model" for large decode caches.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    # Megatron-style sequence parallelism: the residual stream is kept
    # sequence-sharded on "model" at layer boundaries (remat saves 1/16 the
    # activations; XLA inserts all-gather/reduce-scatter at the transitions
    # into/out of attention and TP matmuls).
    "act_seq": "model",
    # MoE token groups: the (batch x seq) reshape inherits the full product
    # sharding; named so dispatch/combine einsums stay local and the
    # expert-major reshard is an explicit all-to-all boundary. The _pm/_pod
    # stages keep the "pod" component in place during the expert reshard —
    # without them the multi-pod partitioner gathers the full token array.
    "tokens": ("pod", "data", "model"),
    "tokens_pm": ("pod", "model"),
    "pod_tokens": ("pod",),
    "kv_seq": "model",  # decode-cache sequence dim (distributed flash-decode)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    # experts span the data axis and expert-ff the model axis, so MoE weights
    # shard over ALL chips (llama4's 387B of experts cannot live on 16): the
    # token->expert boundary becomes an all-to-all across "data". When the
    # expert count does not divide "data" (grok: 8 experts on 16), the
    # shape-aware resolver falls back to sharding the expert d_model dim
    # ("expert_embed") over "data" instead — 2D expert tensor parallelism.
    "experts": "data",
    "expert_mlp": "model",
    "expert_embed": "data",
    "capacity": None,
    "layers": None,
    "rnn": "model",  # xLSTM / RG-LRU feature dim
    "conv": None,
    "window": None,
    "stack": None,
    "zero": ("pod", "data"),  # extra axis for ZeRO-1 optimizer states
    None: None,
}

#: pure data parallelism: small models (~<4B) replicate weights and put the
#: whole mesh behind the batch; ZeRO-1 shards optimizer state over all chips.
DP_RULES = {
    **{k: None for k in DEFAULT_RULES},
    "batch": ("pod", "data", "model"),
    "zero": ("pod", "data", "model"),
}

#: serving activations: every logical axis replicated. The serving engine
#: lowers its executables under these rules, so the model code's
#: constrain() calls resolve to replication: batch inputs, decode caches and
#: the residual stream stay whole on every chip, and the decode cache is
#: never sequence-sharded out from under the pools. The weights are laid out
#: by ``serving_spec`` instead: tensor parallelism lives in analog_dot's
#: shard_map (column-parallel matmul shards whose counter-based noise is
#: salted on global tile coordinates).
SERVING_RULES = {k: None for k in DEFAULT_RULES}

PROFILES = {"tp": DEFAULT_RULES, "dp": DP_RULES, "serving": SERVING_RULES}

_state = threading.local()


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def set_rules(rules: Optional[dict]) -> None:
    _state.rules = rules


def get_rules() -> dict:
    return getattr(_state, "rules", None) or DEFAULT_RULES


@contextlib.contextmanager
def use_rules(rules: Optional[dict]):
    prev = getattr(_state, "rules", None)
    set_rules(rules)
    try:
        yield
    finally:
        set_rules(prev)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None):
    prev = get_mesh()
    prev_rules = getattr(_state, "rules", None)
    set_mesh(mesh)
    if rules is not None:
        set_rules(rules)
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        set_mesh(prev)
        set_rules(prev_rules)


def _candidates(axis: Optional[str], rules: dict, mesh: Mesh):
    phys = rules.get(axis, None)
    if phys is None:
        return ()
    if isinstance(phys, str):
        phys = (phys,)
    return tuple(a for a in phys if a in mesh.axis_names)


def spec(
    names: Sequence[Optional[str]],
    rules: Optional[dict] = None,
    mesh=None,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Logical axis names -> PartitionSpec under the current mesh.

    Shape-aware: a mesh axis is only assigned to a dim if (a) the dim size is
    divisible by the (product of) mesh axis sizes — jit argument shardings
    require exact divisibility — and (b) the mesh axis is not already used by
    an earlier dim of the same tensor (conflict resolution in dim order,
    which is what lets grok's 8 experts fall back to 2D d_model sharding).
    Tuples degrade to their longest feasible prefix. Without a shape, no
    divisibility filtering is applied.
    """
    mesh = mesh or get_mesh()
    rules = rules or get_rules()
    if mesh is None:
        return P()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set = set()
    out = []
    for i, n in enumerate(names):
        cand = tuple(a for a in _candidates(n, rules, mesh) if a not in used)
        chosen = None
        if cand:
            if shape is None:
                chosen = cand
            else:
                dim = shape[i]
                for k in range(len(cand), 0, -1):
                    prefix = cand[:k]
                    prod = 1
                    for a in prefix:
                        prod *= sizes[a]
                    if prod > 1 and dim % prod == 0:
                        chosen = prefix
                        break
        if chosen:
            used.update(chosen)
            out.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            out.append(None)
    return P(*out)


def constrain(x: jax.Array, *names: Optional[str], rules: Optional[dict] = None) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return x
    s = NamedSharding(mesh, spec(names, rules, mesh, shape=x.shape))
    return jax.lax.with_sharding_constraint(x, s)


def named_sharding(
    names: Sequence[Optional[str]],
    mesh: Optional[Mesh] = None,
    rules=None,
    shape: Optional[Sequence[int]] = None,
):
    mesh = mesh or get_mesh()
    if mesh is None:
        raise ValueError("no mesh active")
    return NamedSharding(mesh, spec(names, rules, mesh, shape=shape))


def tree_shardings(axes_tree, shapes_tree=None, mesh: Optional[Mesh] = None, rules=None):
    """Map a tree of logical-axis tuples (+ optional matching shapes tree)
    to a tree of NamedShardings."""
    mesh = mesh or get_mesh()
    is_leaf = lambda x: isinstance(x, tuple)
    if shapes_tree is None:
        return jax.tree.map(
            lambda names: named_sharding(names, mesh, rules), axes_tree, is_leaf=is_leaf
        )
    return jax.tree.map(
        lambda names, sds: named_sharding(
            names, mesh, rules, shape=getattr(sds, "shape", sds)
        ),
        axes_tree,
        shapes_tree,
        is_leaf=is_leaf,
    )


def serving_spec(names: Sequence[Optional[str]], shape: Sequence[int], mesh) -> P:
    """A weight's layout for tensor-parallel serving: ``DEFAULT_RULES``'
    spec, except that a matrix of the layer stack (logical axes ``("layers",
    ..., rows, columns)``) is split by its columns and never by its
    contracting rows. The analog dot is column-parallel only: each chip
    computes whole output columns, with no sum across chips, which keeps a
    mesh engine's tokens bit-identical to one device's. A row split that the
    columns cannot take (they do not divide) leaves the matrix whole.
    Embedding, head and norm scales keep ``DEFAULT_RULES``' layout."""
    out = list(spec(names, DEFAULT_RULES, mesh, shape=shape))
    out += [None] * (len(shape) - len(out))
    if len(shape) >= 3 and names[0] == "layers" and out[-2] is not None:
        axes = out[-2] if isinstance(out[-2], tuple) else (out[-2],)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n = 1
        for a in axes:
            n *= sizes[a]
        out[-2] = None
        if out[-1] is None and shape[-1] % n == 0:
            out[-1] = axes if len(axes) > 1 else axes[0]
    return P(*out)


def serving_shardings(axes_tree, shapes_tree, mesh: Mesh):
    """``serving_spec`` over a tree of logical-axis tuples and the matching
    shapes (arrays or ShapeDtypeStructs), as NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda names, a: NamedSharding(mesh, serving_spec(names, a.shape, mesh)),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def zero1_axes(axes: Tuple[Optional[str], ...]) -> Tuple[Optional[str], ...]:
    """Optimizer-state axes for a param: add 'zero' sharding on the largest
    still-replicated dim (ZeRO-1). Prefers the first None axis of rank>=1."""
    if not axes:
        return axes
    out = list(axes)
    for i, a in enumerate(out):
        if a is None:
            out[i] = "zero"
            return tuple(out)
    return tuple(out)
