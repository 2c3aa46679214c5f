"""Quickstart: the paper's mechanism in one file.

1. Analog matmuls under shot / thermal / weight noise (Eqs. 9-11),
2. the redundant-coding law (noise std ~ 1/sqrt(E)),
3. learning per-layer energies with the Eq.-14 penalty on a tiny frozen MLP,
4. dynamic vs uniform accuracy at the same energy budget.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AnalogConfig,
    CalibConfig,
    analog_dot,
    avg_energy_per_mac,
    dense_site_macs,
    eval_accuracy,
    learn_energies,
    site_key,
    to_energy,
    uniform_log_energies,
)
from repro.data import make_tabular_dataset
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

key = jax.random.PRNGKey(0)

# --- 1. analog matmuls -------------------------------------------------------
x = jax.random.normal(key, (4, 64))
w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32)) * 0.2
clean = x @ w
for name, cfg in [
    ("shot    (2 aJ/MAC)", AnalogConfig.shot()),
    ("thermal (sigma=.01)", AnalogConfig.thermal(0.01)),
    ("weight  (sigma=.1) ", AnalogConfig.weight(0.1)),
]:
    y = analog_dot(x, w, cfg=cfg, energy=jnp.asarray(2.0), key=key)
    print(f"{name}: mean|err| = {float(jnp.abs(y - clean).mean()):.4f}")

# --- 2. redundant coding: noise ~ 1/sqrt(E) ---------------------------------
cfg = AnalogConfig.shot()
for e in (1.0, 4.0, 16.0):
    ys = jax.vmap(lambda k: analog_dot(x, w, cfg=cfg, energy=jnp.asarray(e), key=k))(
        jax.random.split(key, 64)
    )
    print(f"E = {e:5.1f} aJ/MAC -> noise std {float(jnp.std(ys - clean[None])):.4f}")

# --- 3. learn per-layer energies on a frozen model (Eq. 14) -----------------
print("\ntraining a small MLP on a synthetic task ...")
dims = [32, 64, 64, 8]
xd, yd = make_tabular_dataset(4096, dim=32, n_classes=8, depth=2, seed=3)
xd, yd = jnp.asarray(xd), jnp.asarray(yd)
sizes = list(zip(dims[:-1], dims[1:]))
params = [
    jax.random.normal(k, s) / np.sqrt(s[0])
    for k, s in zip(jax.random.split(key, 3), sizes)
]


def loss_fn(p, xb, yb):
    h = xb
    for i, wi in enumerate(p):
        h = h @ wi
        if i < len(p) - 1:
            h = jax.nn.relu(h)
    logp = jax.nn.log_softmax(h)
    return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))


opt = jax.jit(lambda p, xb, yb: jax.tree.map(lambda w_, g: w_ - 0.5 * g, p, jax.grad(loss_fn)(p, xb, yb)))
for _ in range(1200):
    params = opt(params, xd[:3072], yd[:3072])


def apply_fn(energies, xb, k):
    h = xb
    for i, wi in enumerate(params):
        h = analog_dot(h, wi, cfg=cfg, energy=energies[f"l{i}"],
                       key=site_key(jax.random.fold_in(k, i), f"l{i}"))
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


macs = {f"l{i}": dense_site_macs(1, a, b, per_channel=False) for i, (a, b) in enumerate(sizes)}
test = [(xd[3072:], yd[3072:])]
batches = [(xd[i : i + 256], yd[i : i + 256]) for i in range(0, 3072, 256)]

target = 0.1  # aJ/MAC
uniform = to_energy(uniform_log_energies(macs, target))
acc_uni = eval_accuracy(apply_fn, uniform, test, key=key, n_noise_samples=16)

energies, diag = learn_energies(
    apply_fn, macs, batches, key=key, target_e_per_mac=target,
    cfg=CalibConfig(lam=20.0, lr=0.05, steps=200, init_mult=4.0),
)
acc_dyn = eval_accuracy(apply_fn, energies, test, key=key, n_noise_samples=16)

print(f"\nbudget {target} aJ/MAC:")
print(f"  uniform  precision: acc = {acc_uni:.3f}")
print(f"  dynamic  precision: acc = {acc_dyn:.3f} "
      f"(achieved {diag['avg_e_per_mac']:.3f} aJ/MAC)")
print("  learned allocations (aJ/MAC):",
      {k: round(float(v), 3) for k, v in energies.items()})
print("\n-> the middle layer tolerates more noise; the first/last layers get "
      "the energy (paper Fig. 6).")
