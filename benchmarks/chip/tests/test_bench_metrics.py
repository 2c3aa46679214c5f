"""Each per-layer metric reader against numbers worked out by hand."""
import os
import types

import numpy as np
import pytest

import bench_paths
import run

PEAK = dict(bf16_flops=197e12, hbm_bytes_s=819e9)
# a toy model: one layer, q/k/v/o and a SwiGLU MLP
DIMS = dict(n_layers=1, d_model=4, n_heads=2, n_kv_heads=1, head_dim=2,
            d_ff=8, vocab=10, mlp="swiglu")


def _metric(name):
    return run.load_module(os.path.join(bench_paths.CHIP, "metrics", f"{name}.py"),
                           "m_" + name.replace(".", "_")).read


def _track(L, due, admitted, times):
    req = types.SimpleNamespace(prompt=np.zeros(L, np.int32))
    return types.SimpleNamespace(req=req, due=due, admitted=admitted, times=times)


@pytest.fixture
def ctx():
    # host clock in seconds, trace clock = host ns + 1000
    pumps = [(10.0, 10.1), (10.2, 10.3), (10.4, 10.5), (11.5, 11.6)]
    prefills = [(10.0, 2, 16, 20)]  # one dispatch: bb 2, sb 16, 20 real tokens
    ops = [("%fusion.1 = ...", 10_000_001_000, 20_000_000),          # admitting pump
           ('%k = f32 custom-call(...), custom_call_target="tpu_custom_call"',
            10_030_001_000, 40_000_000),                            # kernel, same pump
           ("%fusion.2 = ...", 10_200_001_000, 10_000_000),          # decode-only pump
           ("%fusion.3 = ...", 10_400_001_000, 30_000_000)]          # decode-only pump
    tracks = [_track(8, 9.95, 10.0, [10.1, 10.3, 10.5]),
              _track(12, 9.9, 10.0, [10.1, 10.3])]
    return dict(trace=dict(devices={"/device:TPU:0": dict(ops=ops)}, host=[],
                           window=(10_000_001_000, 11_000_001_000)),
                summary=dict(busy_s=0.1, window_s=1.0), t0=10.0, t1=11.0,
                offset_ns=1000, drive=dict(pumps=pumps, prefills=prefills, tracks=tracks),
                counters=(dict(decode_slot_steps=100, active_slot_steps=40),
                          dict(decode_slot_steps=164, active_slot_steps=88)),
                dims=DIMS, peak=PEAK)


def test_queue_wait_median(ctx):
    assert _metric("queue_wait_ms_p50.chat")(ctx) == pytest.approx(75.0)


def test_pool_occupancy(ctx):
    assert _metric("pool_occupancy.sat")(ctx) == 48 / 64


def test_decode_step(ctx):
    # the two pumps inside the window that admitted nothing: 10 ms and 30 ms
    assert _metric("decode_step_ms.chat")(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["prefill_us_per_tok.sat", "prefill_us_per_tok.chat"])
def test_prefill_per_token(ctx, name):
    # admitting pump busy 60 ms, less one 20 ms decode step, over 20 tokens
    assert _metric(name)(ctx) == pytest.approx(2000.0)


def test_kernel_roofline(ctx):
    m = 2 * 16
    shapes = [(4, 4), (4, 2), (4, 2), (4, 4), (4, 8), (4, 8), (8, 4)]  # q k v o gate up down
    least = sum(max(2 * m * k * n / 197e12, 2 * (m * k + k * n + m * n) / 819e9)
                for k, n in shapes)
    assert _metric("analog_kernel_roofline.sat")(ctx) == pytest.approx(100 * least / 0.040)


def test_idle_share(ctx):
    assert _metric("device_idle_share.sat")(ctx) == pytest.approx(90.0)


def test_mfu(ctx):
    per_tok = 2 * (16 + 8 + 8 + 16 + 32 + 32 + 32)  # 2 x matmul params of the layer
    att = 4 * 1 * 2 * 2  # 4 L H hd per context position
    head = 2 * 4 * 10
    prompt = lambda L: per_tok * L + att * L * (L + 1) / 2 + head
    decode = lambda ctx_len: per_tok + att * ctx_len + head
    # both prompts admitted at 10.0; decode tokens at 10.3 and 10.5 (first
    # track, contexts 9 and 10) and at 10.3 (second track, context 13)
    work = prompt(8) + prompt(12) + decode(9) + decode(10) + decode(13)
    assert _metric("mfu.sat")(ctx) == pytest.approx(100 * work / 1.0 / 197e12)
