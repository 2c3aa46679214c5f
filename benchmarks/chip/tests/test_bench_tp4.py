"""The four-chip cell ``granite8b-tp4-sat`` found by name, and the reader of
``collective_share.tp4`` against a share worked out by hand."""
import os

import pytest

import bench_paths
import run


def _read(ctx):
    return run.load_module(os.path.join(bench_paths.CHIP, "metrics", "collective_share.tp4.py"),
                           "m_collective_share_tp4").read(ctx)


def _ctx(ops, window=(1_000, 2_000)):
    # the first plane is the cell's first chip; the second must not count
    other = [("%all-gather.9 = ...", 1_000, 1_000)]
    return dict(trace=dict(devices={"/device:TPU:0": dict(ops=ops),
                                    "/device:TPU:1": dict(ops=other)},
                           host=[], window=window))


def test_collective_share_by_hand():
    ops = [
        ("%fusion.1 = bf16[32,1024] fusion(...)", 1_000, 200),        # compute 1000-1200
        ("%all-gather-start.3 = (...) all-gather-start(...)", 1_200, 10),  # 1200-1210
        ("%all-gather-done.3 = bf16[32,4096] all-gather-done(...)", 1_210, 90),  # 1210-1300
        ("%all-reduce.7 = f32[] all-reduce(...)", 1_250, 100),        # 1250-1350, overlaps
        ("%bitcast_all-gather_fusion.2 = ...", 1_500, 50),            # named after one
        ("%fusion.2 = f32[32,4096] fusion(...)", 1_540, 160),         # 1540-1700
        ("%while.4 = (...) while(...)", 1_000, 700),                  # container: busy only
        ("%all-to-all.1 = ...", 1_950, 100),                          # half outside
    ]
    # busy: the while covers 1000-1700, then 1950-2000 -> 750 ns
    # collectives: 1200-1350 (150), 1500-1550 (50), 1950-2000 (50) -> 250 ns
    assert _read(_ctx(ops)) == pytest.approx(100.0 * 250 / 750)


def test_collective_share_none_and_zero():
    assert _read(_ctx([])) is None
    assert _read(_ctx([("%fusion.1 = ...", 1_000, 500)])) == 0.0


def test_tp4_cell_loads_with_its_metrics():
    spec = run.load_cell("granite8b-tp4-sat")
    assert spec["cell"]["chips"] == 4 and spec["cfg"]["name"] == "granite-3-8b"
    d = spec["ref"].dims(spec["cfg"])
    assert (d["n_layers"], d["d_model"], d["d_ff"], d["n_heads"], d["n_kv_heads"]) == (
        40, 4096, 12800, 32, 8)
    assert spec["cfg"]["reduced"] == []
    assert spec["mix"]["name"] == "chat-sat" and spec["mix"]["loop"] == "closed"
    assert spec["mix"]["closed"] == {"outstanding": 48, "requests": 4096}
    assert spec["mix"]["engine"]["pool_slots"] == 32 and spec["mix"]["engine"]["max_gen"] == 256
    assert spec["ref"].__name__ == "reference_dense_analog"
    assert spec["limits"]["logit_gap_max"]["limit"] > 0
    assert [m["name"] for m in spec["end_to_end"]] == ["out_tok_s", "setup_s"]
    assert [m["name"] for m in spec["per_layer"]] == [
        "pool_occupancy.sat", "prefill_us_per_tok.sat", "device_idle_share.sat"]
    # the one-chip cells do not take the four-chip config
    assert run.load_cell("granite8b-chat-k1")["cfg"]["num_hidden_layers"] == 20
