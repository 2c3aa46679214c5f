"""Eq.-14 calibration at LM scale: learn per-site energies of a frozen
transformer LM with the distributed calibrate step (the same jitted program
the dry-run lowers for the production mesh, here on the local mesh).

Shows the energy-NLL tradeoff and the learned per-layer-group allocations.

Run:  PYTHONPATH=src python examples/calibrate_lm.py [--target 2.0]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core import AnalogConfig, avg_energy_per_mac, to_energy
from repro.core.energy import uniform_log_energies
from repro.data.pipeline import TokenTaskConfig, markov_batch
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import make_calibrate_step
from repro.models import energy_macs, init_params
from repro.models.config import ModelConfig
from repro.models.sharding import use_mesh
from repro.optim.adam import AdamConfig, adam_init
from repro.runtime.compile_cache import enable_compile_cache

CFG = ModelConfig(
    name="calib-demo", family="dense", n_layers=4, d_model=256, n_heads=8,
    n_kv_heads=4, d_ff=1024, vocab_size=4096, attn_q_chunk=128,
    attn_kv_chunk=128, loss_chunk=128, dtype="float32", remat=False,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", type=float, default=2.0, help="aJ/MAC budget")
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    mesh = make_local_mesh()
    seq = 128
    data = TokenTaskConfig(vocab_size=CFG.vocab_size, seq_len=seq, global_batch=8, seed=7)

    with use_mesh(mesh):
        params = init_params(key, CFG)
        _, jit_for, aux = make_calibrate_step(
            CFG, mesh, analog_cfg=AnalogConfig.shot(), seq_len=seq,
            target_e_per_mac=args.target, lam=20.0, lr=0.05,
        )
        macs = aux["macs"]
        log_e = uniform_log_energies(macs, 4.0 * args.target)
        opt = adam_init(log_e, AdamConfig(lr=0.05))

        batch0 = markov_batch(data, 0)
        specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch0.items()}
        step = jit_for(specs)

        for i in range(args.steps):
            batch = markov_batch(data, i)
            log_e, opt, m = step(log_e, opt, params, batch, jax.random.fold_in(key, i))
            if i % 10 == 0 or i == args.steps - 1:
                e = to_energy(log_e)
                print(f"step {i:>3}: nll {float(m['nll']):.4f}  "
                      f"avg E/MAC {float(avg_energy_per_mac(e, macs)):.3f} aJ")

    e = to_energy(log_e)
    print("\nlearned per-group allocations (aJ/MAC), group 0:")
    for site, v in sorted(e["groups"].items()):
        print(f"  {site:<12} {[round(float(x), 2) for x in jnp.atleast_1d(v)[:4]]}")
    print(f"  lm_head      {float(e['lm_head']):.2f}")


if __name__ == "__main__":
    main()
