"""Plain reference of a dense decoder served under the analog shot-noise model.

Written from the published descriptions, in float32 ``jax.numpy`` with every
matrix product at ``Precision.HIGHEST``, one layer at a time, with no kernel,
no cache and no batching tricks. It imports nothing of the system under test.

What it computes, for a configuration file of ``configs/`` and the analog
settings it states:

* Weights: every leaf drawn as ``normal(key_i) * scale`` in float32 and
  stored in bfloat16, ``key_i`` the i-th of ``split(seed_key, n_leaves)`` over
  the leaves in sorted-key order; norm scales and biases start at zero. This
  is the recipe the served model states for random weights, so one seed gives
  both sides the same bfloat16 values.
* The decoder: RMSNorm with a ``1 + scale`` gain, rotary position embedding
  (half-split rotation, ``theta ** (-i / half)``), causal softmax attention
  with grouped or multi-query KV heads, and a SwiGLU or tanh-GELU MLP.
* Every projection is an analog matrix product (paper arXiv:2102.06365, Eq.
  11): ``y = x @ w + ||x|| * ||w_j|| / sqrt(k * E / E_photon) * xi``, at
  ``E`` aJ per MAC, ``E_photon = hc / 1.55 um``. ``K`` repeats average ``K``
  draws. The output head is digital.
* Two noise streams, chosen per matrix product as the served path states its
  dispatch: the counter stream (Threefry-2x32 over the (row, column) of each
  output element, Box-Muller, repeat ``r`` salted into the second key word)
  for calls of at least 128 rows, columns and depth on a TPU, and
  ``jax.random.normal`` over the output at ``K * E`` otherwise. A request's
  key is folded with the decode position (decode only), then the layer, then
  a 32-bit BLAKE2s hash of the site name. On a mesh of more than one chip
  every product draws the counter stream (``stream_for``).
* On a mesh (``init_weights(..., mesh=)``), each leaf is drawn in the shards
  a tensor-parallel deployment holds: heads, MLP columns and the vocabulary
  split over the mesh's ``model`` axis where it divides them, the rest whole
  on every chip. The values do not depend on the layout (JAX draws with
  ``jax_threefry_partitionable``), and the functions below run over such
  weights as over one chip's.

``logits`` runs prompt plus served tokens teacher-forced and returns the
logits at every position; ``control_dtype`` rounds the operands of every
matrix product to that type first (the control of the comparison).
"""
from __future__ import annotations

import functools
import hashlib
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PHOTON_ENERGY_AJ = 6.62607015e-34 * 2.99792458e8 / 1.55e-6 * 1e18
COUNTER = "counter"
RANDOM = "random"
_REPEAT_MULT = 0x85EBCA6B
_PARITY = 0x1BD11BDA
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
#: smallest rows, depth and columns at which a TPU runs the counter stream
MIN_COUNTER_DIM = 128
#: mesh axis over which a tensor-parallel deployment splits a layer
MODEL_AXIS = "model"


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------


def dims(c: dict) -> dict:
    """Uniform sizes of a configuration file, from its published key names."""
    t = c["model_type"]
    if t == "granite":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return dict(
            n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
            n_kv_heads=c["num_key_value_heads"], head_dim=d // h,
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            mlp="swiglu" if c["hidden_act"] == "silu" else c["hidden_act"],
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        )
    if t == "gpt_bigcode":
        d, h = c["n_embd"], c["n_head"]
        return dict(
            n_layers=c["n_layer"], d_model=d, n_heads=h,
            n_kv_heads=1 if c["multi_query"] else h, head_dim=d // h,
            d_ff=c["n_inner"], vocab=c["vocab_size"],
            mlp="gelu" if c["activation_function"] == "gelu_pytorch_tanh"
            else c["activation_function"],
            rope_theta=float(c["rope_theta"]), eps=float(c["layer_norm_epsilon"]),
        )
    raise ValueError(f"no reference for model_type {t!r}")


def program_kwargs(c: dict) -> dict:
    """Keyword arguments of the served model's configuration for this file."""
    d = dims(c)
    return dict(
        name=c["name"], family="dense", n_layers=d["n_layers"],
        d_model=d["d_model"], n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        head_dim=d["head_dim"], d_ff=d["d_ff"], vocab_size=d["vocab"],
        mlp_type=d["mlp"], rope_theta=d["rope_theta"], norm_eps=d["eps"],
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype="bfloat16",
    )


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 16) * 16


class _Leaf:
    """A weight's shape, its initial scale (0: zeros), and the dim that a
    tensor-parallel deployment splits over ``MODEL_AXIS`` (None: whole)."""

    def __init__(self, shape, scale, split=None):
        self.shape, self.scale, self.split = tuple(shape), float(scale), split


def layout(c: dict) -> dict:
    d = dims(c)
    L, dm, ff = d["n_layers"], d["d_model"], d["d_ff"]
    qd, kd = d["n_heads"] * d["head_dim"], d["n_kv_heads"] * d["head_dim"]
    s = dm ** -0.5
    blocks = {
        "ln1_0": _Leaf((L, dm), 0.0),
        "ln2_0": _Leaf((L, dm), 0.0),
        "attn0": {
            "wq": _Leaf((L, dm, qd), s, 2), "wk": _Leaf((L, dm, kd), s, 2),
            "wv": _Leaf((L, dm, kd), s, 2), "wo": _Leaf((L, qd, dm), qd ** -0.5, 1),
        },
    }
    if d["mlp"] == "swiglu":
        blocks["mlp0"] = {
            "w_gate": _Leaf((L, dm, ff), s, 2), "w_up": _Leaf((L, dm, ff), s, 2),
            "w_down": _Leaf((L, ff, dm), ff ** -0.5, 1),
        }
    else:
        blocks["mlp0"] = {
            "w_in": _Leaf((L, dm, ff), s, 2), "b_in": _Leaf((L, ff), 0.0, 1),
            "w_down": _Leaf((L, ff, dm), ff ** -0.5, 1), "b_out": _Leaf((L, dm), 0.0),
        }
    vp = padded_vocab(d["vocab"])
    tree = {"blocks": blocks, "embed": _Leaf((vp, dm), 0.02, 0), "final_ln": _Leaf((dm,), 0.0)}
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = _Leaf((dm, vp), s, 1)
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _draw_on(sharding):
    """``_draw`` with its output laid out by ``sharding``: each chip draws
    only its own shard."""
    return jax.jit(_draw.__wrapped__, static_argnums=(1, 2), out_shardings=sharding)


def leaf_sharding(leaf: _Leaf, mesh):
    """The leaf's layout on ``mesh``: its ``split`` dim over ``MODEL_AXIS``
    where the axis divides it, whole on every chip otherwise."""
    from jax.sharding import NamedSharding, PartitionSpec

    spec = [None] * len(leaf.shape)
    if leaf.split is not None and leaf.shape[leaf.split] % mesh.shape[MODEL_AXIS] == 0:
        spec[leaf.split] = MODEL_AXIS
    return NamedSharding(mesh, PartitionSpec(*spec))


def init_weights(key, c: dict, mesh=None):
    """bfloat16 weights from a raw PRNG key, one leaf at a time on the device;
    with ``mesh``, each leaf drawn in its shards (``leaf_sharding``)."""
    leaves, treedef = jax.tree.flatten(layout(c), is_leaf=lambda x: isinstance(x, _Leaf))
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, k in zip(leaves, keys):
        sh = None if mesh is None else leaf_sharding(leaf, mesh)
        if leaf.scale == 0.0:
            out.append(jnp.zeros(leaf.shape, jnp.bfloat16, device=sh))
        else:
            out.append((_draw if sh is None else _draw_on(sh))(k, leaf.shape, leaf.scale))
    return treedef.unflatten(out)


def site_hash(site: str) -> int:
    return int.from_bytes(hashlib.blake2s(site.encode(), digest_size=4).digest(), "little")


def stream_for(platform: str, backend: str, m: int, k: int, n: int, chips: int = 1) -> str:
    """Noise stream of one matrix product of ``m`` rows, as the served path
    states its dispatch, on ``chips`` chips."""
    if backend in ("pallas", "tile"):
        return COUNTER
    if backend == "jnp":
        return RANDOM
    # "auto" on a tensor-parallel mesh: every product too small for the
    # fused kernel (m = 1 decode among them) goes to the tile oracle, which
    # draws the counter stream and can be split by columns; the random
    # stream cannot, so the served path never draws it there
    if chips > 1:
        return COUNTER
    if platform == "tpu" and min(m, k, n) >= MIN_COUNTER_DIM:
        return COUNTER
    return RANDOM


# --------------------------------------------------------------------------
# the counter stream: Threefry-2x32 (20 rounds) and Box-Muller
# --------------------------------------------------------------------------


def _rotl(x, d):
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def threefry2x32(k0, k1, c0, c1):
    ks2 = k0 ^ k1 ^ jnp.uint32(_PARITY)
    x0, x1 = c0 + k0, c1 + k1
    sched = ((k1, ks2, 1), (ks2, k0, 2), (k0, k1, 3), (k1, ks2, 4), (ks2, k0, 5))
    for i, (a, b, n) in enumerate(sched):
        for d in (_ROT_A if i % 2 == 0 else _ROT_B):
            x0 = x0 + x1
            x1 = _rotl(x1, d) ^ x0
        x0 = x0 + a
        x1 = x1 + b + jnp.uint32(n)
    return x0, x1


def counter_normal(k0, k1, rows, cols):
    """One standard normal per (row, column) counter pair."""
    b0, b1 = threefry2x32(k0, k1, rows, cols)
    top0 = (b0 >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    top1 = (b1 >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    u1 = 1.0 - top0 * jnp.float32(2.0 ** -24)
    u2 = top1 * jnp.float32(2.0 ** -24)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * 3.14159265358979) * u2)


# --------------------------------------------------------------------------
# one layer
# --------------------------------------------------------------------------


def _fold(keys, data):
    """fold_in over a (..., 2) array of raw keys with (...) data."""
    flat = keys.reshape(-1, 2)
    d = jnp.broadcast_to(jnp.asarray(data, jnp.uint32), keys.shape[:-1]).reshape(-1)
    return jax.vmap(jax.random.fold_in)(flat, d).reshape(keys.shape)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    half = x.shape[-1] // 2
    t = x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _round_to(v, dtype, axis):
    """Round ``v`` to ``dtype`` with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (v / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("n", "k_rep"))
def _counter_noise(words, rows, n, k_rep):
    """Mean of ``k_rep`` counter-stream normals per (row, column), (B, T, n).

    Repeat ``r`` salts the second key word with ``r * 0x85EBCA6B``; the draws
    are added in repeat order and scaled by ``float32(1 / K)``."""
    k0, k1 = words[..., 0:1], words[..., 1:2]
    cols = jnp.arange(n, dtype=jnp.uint32)[None, None, :]
    xi = counter_normal(k0, k1, rows, cols)
    if k_rep > 1:
        def add(r, acc):
            salt = jnp.asarray(r, jnp.uint32) * jnp.uint32(_REPEAT_MULT)
            return acc + counter_normal(k0, k1 ^ salt, rows, cols)

        xi = jax.lax.fori_loop(1, k_rep, add, xi) * jnp.float32(1.0 / k_rep)
    return xi


@functools.partial(jax.jit, static_argnames=("k_rep", "streams", "control"))
def site(x, w, kw_pref, kw_dec, lengths, energy, *, k_rep, streams, control):
    """One analog matrix product with its noise, for every position.

    ``x`` (B, T, k) float32, ``w`` (k, n) bfloat16; ``kw_pref`` (B, 2) the
    site's prefill key of each row, ``kw_dec`` (B, T, 2) its decode key at
    each position; ``lengths`` (B,) prompt lengths."""
    b, t, k = x.shape
    n = w.shape[1]
    w = w.astype(jnp.float32)
    if control is not None:
        x, w = _round_to(x, control, -1), _round_to(w, control, 0)
    y = jnp.einsum("btk,kn->btn", x, w, precision=HIGHEST)
    photons = energy / PHOTON_ENERGY_AJ
    xn = jnp.sqrt(jnp.sum(x * x, -1))[..., None]
    wn = jnp.sqrt(jnp.sum(w * w, 0))
    pos = jnp.arange(t)
    is_prefill = pos[None, :] < lengths[:, None]  # (B, T)
    pre_stream, dec_stream = streams
    if COUNTER in streams:
        # prefill rows count their position; a decode call is one row (0)
        words = jnp.where(is_prefill[..., None], kw_pref[:, None, :], kw_dec)
        rows = jnp.where(is_prefill, pos[None, :], 0).astype(jnp.uint32)[..., None]
        xi = _counter_noise(words, rows, n, k_rep)
        std = xn * wn / jnp.sqrt(jnp.float32(k) * photons)
        sel = jnp.where(is_prefill, pre_stream == COUNTER, dec_stream == COUNTER)
        y = y + jnp.where(sel[..., None], std * xi, 0.0)
    if RANDOM in streams:
        pre = jax.vmap(lambda kk: jax.random.normal(kk, (t, n), jnp.float32))(kw_pref)
        dec = jax.vmap(jax.vmap(lambda kk: jax.random.normal(kk, (1, n), jnp.float32)[0]))(kw_dec)
        xi = jnp.where(is_prefill[..., None], pre, dec)
        std = xn * wn / jnp.sqrt(jnp.float32(k) * photons * k_rep)
        sel = jnp.where(is_prefill, pre_stream == RANDOM, dec_stream == RANDOM)
        y = y + jnp.where(sel[..., None], std * xi, 0.0)
    return y


@functools.partial(jax.jit, static_argnames=("t",))
def layer_keys(keys, li, *, t):
    b = keys.shape[0]
    k_pref = _fold(keys, li)
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    k_dec = _fold(_fold(jnp.broadcast_to(keys[:, None, :], (b, t, 2)), pos), li)
    return k_pref, k_dec


@jax.jit
def fold_site(k_pref, k_dec, h):
    return _fold(k_pref, h), _fold(k_dec, h)


@functools.partial(jax.jit, static_argnames=("eps",))
def rms(x, g, *, eps):
    return _rms(x, g, eps)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta"))
def attention(q, k, v, *, n_heads, n_kv, theta):
    """Causal softmax attention with rotary positions; (B, T, H * hd)."""
    b, t, _ = q.shape
    hd = q.shape[-1] // n_heads
    q = _rope(q.reshape(b, t, n_heads, hd), theta)
    k = _rope(k.reshape(b, t, n_kv, hd), theta)
    v = v.reshape(b, t, n_kv, hd)
    q = q.reshape(b, t, n_kv, n_heads // n_kv, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    return a.reshape(b, t, n_heads * hd)


@jax.jit
def _swiglu(g, u):
    return jax.nn.silu(g) * u


@jax.jit
def _gelu(u, bias):
    return jax.nn.gelu(u + bias.astype(jnp.float32), approximate=True)


@jax.jit
def _add(x, y, bias=None):
    return x + y if bias is None else x + y + bias.astype(jnp.float32)


def layer(x, p, keys, lengths, li, energy, c, *, k_rep, streams, control):
    """One decoder layer over (B, T, d) float32 activations; ``p`` holds the
    layer's weights."""
    d = dims(c)
    k_pref, k_dec = layer_keys(keys, jnp.int32(li), t=x.shape[1])
    kw = dict(k_rep=k_rep, streams=streams, control=control)

    def dot(name, h, w):
        kp, kd = fold_site(k_pref, k_dec, jnp.uint32(site_hash(name)))
        return site(h, w, kp, kd, lengths, energy, **kw)

    h = rms(x, p["ln1_0"], eps=d["eps"])
    at = p["attn0"]
    a = attention(dot("attn0_q", h, at["wq"]), dot("attn0_k", h, at["wk"]),
                  dot("attn0_v", h, at["wv"]), n_heads=d["n_heads"],
                  n_kv=d["n_kv_heads"], theta=d["rope_theta"])
    x = _add(x, dot("attn0_o", a, at["wo"]))
    h = rms(x, p["ln2_0"], eps=d["eps"])
    m = p["mlp0"]
    if d["mlp"] == "swiglu":
        u = _swiglu(dot("mlp0_gate", h, m["w_gate"]), dot("mlp0_up", h, m["w_up"]))
        return _add(x, dot("mlp0_out", u, m["w_down"]))
    u = _gelu(dot("mlp0_in", h, m["w_in"]), m["b_in"])
    return _add(x, dot("mlp0_out", u, m["w_down"]), m["b_out"])


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "control"))
def head(x, final_ln, w_head, *, eps, vocab, control):
    h = _rms(x, final_ln, eps)
    w = w_head.astype(jnp.float32)
    if control is not None:
        h, w = _round_to(h, control, -1), _round_to(w, control, 0)
    return jnp.einsum("btd,dv->btv", h, w, precision=HIGHEST)[..., :vocab]


def logits(weights, c: dict, tokens, keys, lengths, repeats, *, streams,
           energy: float, control: Optional[str] = None):
    """Teacher-forced logits, (B, T, vocab) float32.

    ``tokens`` (B, T) prompt then served tokens (padded at the end),
    ``keys`` (B, 2) uint32 raw request keys, ``lengths`` (B,) prompt lengths,
    ``repeats`` the K of each layer, ``streams`` the (prefill, decode) noise
    streams, ``control`` None or the name of a lower dtype.
    """
    d = dims(c)
    ctl = None if control is None else jnp.dtype(control)
    x = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    keys = jnp.asarray(keys, jnp.uint32)
    lengths = jnp.asarray(lengths, jnp.int32)
    e = jnp.float32(energy)
    for li in range(d["n_layers"]):
        p = jax.tree.map(lambda a: a[li], weights["blocks"])
        x = layer(x, p, keys, lengths, li, e, c, k_rep=int(repeats[li]),
                  streams=tuple(streams), control=ctl)
    w_head = weights["embed"].T if c["tie_word_embeddings"] else weights["lm_head"]
    return head(x, weights["final_ln"], w_head, eps=d["eps"], vocab=d["vocab"], control=ctl)
