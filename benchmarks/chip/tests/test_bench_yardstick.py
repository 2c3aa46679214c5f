"""Operations and bytes from shapes, the table of peaks, and every
configuration, mix and metric of BENCHMARK.json found by name."""
import json
import os
import subprocess
import sys

import pytest

import bench_paths
import flops
import peaks

with open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _dims(name):
    import run

    with open(os.path.join(bench_paths.CHIP, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    ref = run.load_module(os.path.join(bench_paths.CHIP, "reference", f"{cfg['reference']}.py"),
                          "ref_" + cfg["reference"])
    return ref.dims(cfg)


def test_granite_3_8b_layer_by_hand():
    d = _dims("granite-3-8b-L20")
    # q 4096x4096, k and v 4096x1024, o 4096x4096, gate/up 4096x12800, down 12800x4096
    per_layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 4096 * 12800 * 3
    assert flops.layer_matmul_params(d) == per_layer == 199_229_440
    # one 4x512 prefill: 2 m k n per site over 20 layers
    f, b = flops.prefill_kernel_cost(d, 2048)
    assert f == 2 * 2048 * per_layer * 20
    x_y = sum(2048 * k + 2048 * n for _, k, n in flops.matmul_shapes(d))
    assert b == 2 * (x_y + per_layer) * 20
    # a decode token at context 100: layers, attention (4 L H hd ctx) and head
    assert flops.token_flops(d, 100, head=True) == (
        2 * 20 * per_layer + 4 * 20 * 32 * 128 * 100 + 2 * 4096 * 49155)


#: IBM Granite 20B Code base (arXiv:2405.04324; GPTBigCode) at 10 of 52 layers
GRANITE_20B = dict(
    reference="dense_analog", model_type="gpt_bigcode", n_embd=6144, n_head=48,
    multi_query=True, n_inner=24576, activation_function="gelu_pytorch_tanh",
    vocab_size=49152, n_layer=10, rope_theta=10000.0, layer_norm_epsilon=1e-5)


def test_granite_20b_layer_by_hand():
    import run

    ref = run.load_module(os.path.join(bench_paths.CHIP, "reference", "dense_analog.py"),
                          "ref_dense_analog")
    d = ref.dims(GRANITE_20B)
    # q 6144x6144, k and v 6144x128 (MQA), o 6144x6144, in 6144x24576, down 24576x6144
    per_layer = 6144 * 6144 * 2 + 6144 * 128 * 2 + 6144 * 24576 * 2
    assert flops.layer_matmul_params(d) == per_layer == 379_060_224
    assert flops.kernel_call_cost(512, 6144, 128) == (2 * 512 * 6144 * 128,
                                                      2 * (512 * 6144 + 6144 * 128 + 512 * 128))
    assert flops.prompt_flops(d, 3) == (2 * 10 * per_layer * 3 + 4 * 10 * 48 * 128 * (1 + 2 + 3)
                                        + 2 * 6144 * 49152)


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_found_by_name(cell):
    import run

    spec = run.load_cell(cell)
    assert spec["cell"]["name"] == cell
    assert spec["end_to_end"] and spec["per_layer"]
    for m in spec["per_layer"]:
        mod = run.load_module(os.path.join(bench_paths.CHIP, "metrics", f"{m['name']}.py"),
                              "m_" + m["name"].replace(".", "_"))
        assert callable(mod.read)
        moves = {e["name"]: e for e in spec["end_to_end"]}
        assert m["moves"] in moves
    from repro.models.config import ModelConfig

    mc = ModelConfig(**spec["ref"].program_kwargs(spec["cfg"]))
    assert mc.n_layers == spec["ref"].dims(spec["cfg"])["n_layers"]
    for tier in spec["mix"]["tiers"].values():
        import harness

        assert len(harness.tier_repeats(tier, mc.n_layers)) == mc.n_layers


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_result():
    p = _run_py(bench_paths.ROOT, {})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_fail_without_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(bench_paths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_paths.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {})
    assert p.returncode != 0
    assert "{" not in p.stdout
