"""Scan-corrected HLO analysis: parser vs ground truth on an 8-device mesh
(subprocess: the test process must keep its single CPU device)."""
import os
import subprocess
import sys
import textwrap


def _run(code: str):
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_scan_corrected_dot_flops_and_collectives():
    out = _run(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        L, B, D = 7, 32, 64
        def f(x, ws):
            def body(h, w):
                return jnp.tanh(jnp.dot(h, w)), None
            h, _ = jax.lax.scan(body, x, ws)
            return jnp.sum(h)
        x_sh = NamedSharding(mesh, P("data", "model"))
        w_sh = NamedSharding(mesh, P(None, "model", None))
        c = jax.jit(f, in_shardings=(x_sh, w_sh),
                    out_shardings=NamedSharding(mesh, P())).lower(
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
        stats = analyze(c.as_text(), 8)
        gt_flops = 2 * (B // 2) * (D // 4) * D * L   # per-device
        assert abs(stats.dot_flops - gt_flops) / gt_flops < 0.01, stats.dot_flops
        # the raw cost_analysis counts the body once (the bug we correct):
        ca = c.cost_analysis()
        raw = (ca[0] if isinstance(ca, (list, tuple)) else ca)["flops"]
        assert stats.dot_flops > 3 * raw
        # per-layer all-reduce of f32[16,64] ring bytes: 2*(4-1)/4 * 4096 * L
        ar = stats.collective_bytes["all-reduce"]
        gt_ar = 2 * (4 - 1) / 4 * (B // 2) * D * 4 * L
        assert abs(ar - gt_ar) / gt_ar < 0.05, (ar, gt_ar)
        print("PARSER OK")
        """
    )
    assert "PARSER OK" in out


def test_sharded_train_step_matches_single_device():
    """Numerical equivalence: the sharded train step on an 8-device mesh
    produces the same loss/params as the 1-device run."""
    out = _run(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.data.pipeline import TokenTaskConfig, markov_batch
        from repro.launch.steps import TrainConfig, make_train_step
        from repro.models import init_params
        from repro.launch.mesh import make_mesh_for_devices
        from repro.models.sharding import use_mesh
        from repro.optim.adam import adam_init

        cfg = dataclasses.replace(get_smoke_config("grok-1-314b"), dtype="float32")
        data = TokenTaskConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=1)
        batch = markov_batch(data, 0)
        tcfg = TrainConfig(lr=1e-3, opt_state_dtype="float32")
        results = {}
        for shape in ((1, 1), (2, 4)):
            mesh = make_mesh_for_devices(shape[0] * shape[1], model_parallel=shape[1])
            with use_mesh(mesh):
                params = init_params(jax.random.PRNGKey(0), cfg)
                _, jit_for, _ = make_train_step(cfg, mesh, tcfg)
                specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
                step = jit_for(specs)
                opt = adam_init(params, tcfg.adam())
                p2, _, m = step(params, opt, batch)
                results[shape] = (jax.device_get(p2), float(m["loss"]))
        l1, l8 = results[(1, 1)][1], results[(2, 4)][1]
        assert abs(l1 - l8) < 1e-3, (l1, l8)
        diffs = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
                             results[(1, 1)][0], results[(2, 4)][0])
        worst = max(jax.tree.leaves(diffs))
        assert worst < 5e-3, worst
        print("SHARDED OK", l1, l8, worst)
        """
    )
    assert "SHARDED OK" in out
