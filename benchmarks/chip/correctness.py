"""The comparison that decides ``correct``: served tokens against the reference.

Once the window has closed and the program's device state is freed, a sample
of the finished requests, drawn from the seed and always holding the longest,
is run through the plain reference (``reference/``) teacher-forced: each
prompt followed by the tokens the engine served for it, under the request's
own noise key and tier. For every served token the reference gives its
logits at that position; the number compared is the widest gap, over every
sampled token, by which the served token's logit lies below the reference's
best (greedy decoding serves the best, so a faithful run reads rounding).
A cell of several chips runs the reference on the program's mesh, its
weights drawn in their shards there, so no chip holds the whole model.
The control is the same reference with the operands of every matrix product
rounded to a lower precision: at each of the same positions it puts its own
best token first, and its gap is read the same way.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import numpy as np

import flops
import harness
import traffic as traffic_lib

BATCH = 4  # sequences per reference call


def sample(tracks, seed: int, want_tokens: int, max_requests: int) -> list:
    """Finished requests: the longest first, then others in an order drawn
    from the seed, until ``want_tokens`` served tokens."""
    done = [t for t in tracks if t.tokens is not None]
    if not done:
        return []
    done.sort(key=lambda t: (-t.tokens.size, t.req.index))
    rest = done[1:]
    order = np.random.default_rng((seed, 0xC0)).permutation(len(rest))
    out = [done[0]]
    n = done[0].tokens.size
    for i in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i].tokens.size
    return out


@functools.lru_cache(maxsize=None)
def _gap_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(lg, ctl, targets, mask):
        best = jnp.max(lg, -1)
        served = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        agree = (jnp.argmax(lg, -1) == targets) & mask
        g = jnp.where(mask, best - served, -jnp.inf)
        if ctl is None:
            return jnp.max(g, -1), jnp.sum(agree, -1), None
        pick = jnp.take_along_axis(lg, jnp.argmax(ctl, -1)[..., None], -1)[..., 0]
        gc = jnp.where(mask, best - pick, -jnp.inf)
        return jnp.max(g, -1), jnp.sum(agree, -1), jnp.max(gc, -1)

    return gaps


def compare(ref, cfg: dict, mix: dict, seed: int, picked: list, *, platform: str,
            control: Optional[str] = None, mesh=None) -> Dict[str, float]:
    """Run the reference over ``picked`` tracks (on ``mesh``, where the
    program ran on one); returns the readings."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    d = ref.dims(cfg)
    a = cfg["analog"]
    weights = ref.init_weights(harness.weight_key(seed), cfg, mesh=mesh)
    chips = 1 if mesh is None else mesh.size
    jax.block_until_ready(weights)
    ladder = mix["engine"]["seq_buckets"]
    by_tier: Dict[str, List] = {}
    for t in picked:
        by_tier.setdefault(t.req.tier, []).append(t)
    gap_max, ctl_max, n_tok, n_agree = 0.0, 0.0, 0, 0
    for tier, group in sorted(by_tier.items()):
        reps = harness.tier_repeats(mix["tiers"][tier], d["n_layers"])
        m_pre = traffic_lib.bucket(max(t.req.prompt.size for t in group), ladder)
        k_min = min(min(k, n) for _, k, n in flops.matmul_shapes(d))
        streams = (ref.stream_for(platform, a["backend"], m_pre, k_min, k_min, chips),
                   ref.stream_for(platform, a["backend"], 1, k_min, k_min, chips))
        # one fixed length per mix, so every run reuses the compiled reference
        T = -(-(max(ladder) + mix["engine"]["max_gen"]) // 128) * 128
        for i in range(0, len(group), BATCH):
            rows = group[i:i + BATCH]
            pad = rows + [rows[0]] * (BATCH - len(rows))
            full = np.zeros((BATCH, T + 1), np.int32)
            mask = np.zeros((BATCH, T), bool)
            for b, t in enumerate(pad):
                seq = np.concatenate([t.req.prompt, t.tokens]).astype(np.int32)
                full[b, :seq.size] = seq
                L = t.req.prompt.size
                mask[b, L - 1:L - 1 + t.tokens.size] = b < len(rows)
            keys = np.stack([t.req.key for t in pad]).astype(np.uint32)
            lens = np.array([t.req.prompt.size for t in pad], np.int32)
            kw = dict(streams=streams, energy=float(a["energy_aj_per_mac"]))
            lg = ref.logits(weights, cfg, full[:, :T], keys, lens, reps, **kw)
            ctl = None
            if control is not None:
                ctl = ref.logits(weights, cfg, full[:, :T], keys, lens, reps,
                                 control=control, **kw)
            g, agree, gc = _gap_fn()(lg, ctl, jnp.asarray(full[:, 1:]), jnp.asarray(mask))
            g, agree = np.asarray(g), np.asarray(agree)
            for b, t in enumerate(rows):
                gap_max = max(gap_max, float(g[b]))
                n_tok += t.tokens.size
                n_agree += int(agree[b])
                if gc is not None:
                    ctl_max = max(ctl_max, float(np.asarray(gc)[b]))
            del lg, ctl
    out = dict(logit_gap_max=gap_max, tokens_compared=n_tok,
               requests_compared=len(picked),
               top1_agreement=n_agree / max(n_tok, 1),
               reference_s=time.perf_counter() - t0)
    if control is not None:
        out["control_gap_max"] = ctl_max
    return out
