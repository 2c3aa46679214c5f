"""End-to-end training driver example.

Trains a transformer LM on the deterministic synthetic Markov task through
the full production stack: sharded train step (TP+SP rules on a local mesh),
fault-tolerant driver, atomic checkpoints, straggler monitoring.

Default is a ~10M-param model for a quick CPU demo; ``--model 100m`` selects
a ~100M-param config (same code path, the few-hundred-step run the
deliverable describes — budget ~1-2h on this CPU container; on a real TPU
slice it is minutes).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200] [--model 10m]
"""
import argparse
import os
import tempfile

from repro.data.pipeline import TokenTaskConfig
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import TrainConfig
from repro.models.config import ModelConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.driver import DriverConfig, TrainDriver

MODELS = {
    "10m": ModelConfig(
        name="demo-10m", family="dense", n_layers=4, d_model=256, n_heads=8,
        n_kv_heads=4, d_ff=1024, vocab_size=4096, attn_q_chunk=128,
        attn_kv_chunk=128, loss_chunk=128,
    ),
    "100m": ModelConfig(
        name="demo-100m", family="dense", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, d_ff=3072, vocab_size=32768, attn_q_chunk=256,
        attn_kv_chunk=256, loss_chunk=256,
    ),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="10m", choices=sorted(MODELS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = MODELS[args.model]
    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params")
    data = TokenTaskConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch, seed=7,
    )
    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(), f"repro_{cfg.name}")
    driver = TrainDriver(
        cfg, data, make_local_mesh(),
        ckpt_dir=ckpt_dir,
        train_cfg=TrainConfig(lr=3e-4, opt_state_dtype="float32"),
        driver_cfg=DriverConfig(
            max_steps=args.steps, ckpt_every=50, ckpt_async=True, log_every=10
        ),
    )
    out = driver.run()
    print("step  loss    step_time")
    for m in out["metrics"]:
        print(f"{m['step']:>5} {m['loss']:.4f}  {m['dt']*1e3:.0f} ms")
    print(f"checkpoints in {ckpt_dir}; straggler flags: {len(driver.monitor.flags)}")


if __name__ == "__main__":
    main()
