"""What decides ``correct``, driven end to end on the CPU at a small size.

Each test skips the harness's look for a chip and runs the rest of a run
(``run.run``): warm-up, window, drain, reference. A sound run reads a gap at
rounding level; the control (the reference at float8) and a timed path
broken underneath (a decode step that alters the token it produces, or that
returns the cache unchanged) read far above the limit, so ``correct`` is
false.
"""
import argparse
import copy
import json
import os

import pytest

import bench_paths  # noqa: F401
import harness
import peaks
import run
import traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LIMIT = 0.06  # here sound runs read about 0.01 (bf16 rounding) and fp8 about 0.2


@pytest.fixture(scope="module")
def spec():
    import jax

    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "tiny_mix.json")) as f:
        mix = json.load(f)
    ref = run.load_module(os.path.join(bench_paths.CHIP, "reference", "dense_analog.py"),
                          "ref_dense_analog")
    e2e = [dict(name="ttft_p95_ms", unit="ms"), dict(name="out_tok_s", unit="tokens/s")]
    peaks.PEAKS.setdefault(jax.devices()[0].device_kind,
                           dict(bf16_flops=1e12, hbm_bytes_s=1e11, hbm_bytes=1e10, source="test"))
    return dict(cell=dict(chips=1), cfg=cfg, mix=mix, ref=ref, end_to_end=e2e,
                per_layer=[], limits={"logit_gap_max": {"limit": LIMIT}})


def _run(spec, capsys, control=None, backend="auto", seed=2**31 + 99, tie=False):
    import jax

    s = copy.deepcopy({k: v for k, v in spec.items() if k != "ref"})
    s["ref"] = spec["ref"]
    s["cfg"]["analog"]["backend"] = backend
    s["cfg"]["tie_word_embeddings"] = tie
    args = argparse.Namespace(workload="tiny", seed=seed, seconds=0.5, trace=0,
                              control=control, keep_trace=None)
    assert run.run(args, s, jax.devices(), harness.CompileCounter()) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[-1] == "compared"
    return res, err


def test_sound_run_is_correct_and_control_fails(spec, capsys):
    res, err = _run(spec, capsys)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    gap = res["compared"]["logit_gap_max"]
    assert gap["value"] <= LIMIT and gap["limit"] == LIMIT
    assert {"ttft_p95_ms", "out_tok_s", "setup_s"} <= set(res["metrics"])
    # the control in the program's place: the same run reads not correct
    res, err = _run(spec, capsys, control="float8_e4m3fn")
    assert not res["correct"] and res["failed"] == 0
    ctl = res["compared"]["logit_gap_max"]["value"]
    assert ctl > LIMIT and ctl == _check(err, "control_gap_max")
    assert _check(err, "logit_gap_max") <= LIMIT


def _check(err, name):
    return float(next(l for l in err.splitlines() if l.startswith(f"check {name}:")).split()[-1])


def test_counter_stream_run_is_correct(spec, capsys):
    res, _ = _run(spec, capsys, backend="tile", seed=7)
    assert res["correct"] and res["compared"]["logit_gap_max"]["value"] <= LIMIT


def test_tied_head_run_is_correct(spec, capsys):
    res, _ = _run(spec, capsys, seed=2**31 + 5, tie=True)
    assert res["correct"] and res["compared"]["logit_gap_max"]["value"] <= LIMIT


def test_program_state_freed_before_reference(spec, capsys, monkeypatch):
    # the reference runs on the chip once the window has closed: the
    # program's device arrays have to be gone by then, or it does not fit
    import jax

    import correctness

    before = sum(x.nbytes for x in jax.live_arrays())
    seen = []
    orig = correctness.compare

    def compare(*args, **kwargs):
        seen.append(sum(x.nbytes for x in jax.live_arrays()))
        return orig(*args, **kwargs)

    monkeypatch.setattr(correctness, "compare", compare)
    res, _ = _run(spec, capsys, seed=2**31 + 17)
    assert res["correct"] and len(seen) == 1 and seen[0] <= before


def _broken_decode(monkeypatch, how):
    from repro.serving import tiers

    orig = tiers.ExecutionTier.build_decode

    def build(self, bb, cache_len):
        exe = orig(self, bb, cache_len)

        def call(params, cache, tok, *rest):
            if how == "token":
                nxt, new = exe(params, cache, tok, *rest)
                return (nxt + 1) % self.engine.model_cfg.vocab_size, new
            # the step leaves the cache as it found it (it runs on a copy,
            # since the executable may update its donated input in place)
            import jax
            import jax.numpy as jnp

            nxt, _ = exe(params, jax.tree.map(jnp.copy, cache), tok, *rest)
            return nxt, cache

        return call

    monkeypatch.setattr(tiers.ExecutionTier, "build_decode", build)


@pytest.mark.parametrize("how", ["token", "state"])
def test_broken_timed_path_is_not_correct(spec, capsys, monkeypatch, how):
    _broken_decode(monkeypatch, how)
    res, _ = _run(spec, capsys, seed=11)
    assert not res["correct"]
    assert res["compared"]["logit_gap_max"]["value"] > LIMIT


@pytest.mark.parametrize("close", [4.9, 5.1])  # either side of a token at 5.0
def test_out_tok_s_takes_the_drained_window(close):
    """The rate counts every token of every request sent, over the window's
    open to the drain's end: where the close falls inside a pump does not
    step it."""
    req = traffic.Request(0, None, 4, "k1", None, 0.0)
    tracks = [harness.Track(req, uid, due=0.0, times=[2.0 * uid + 1.0 + i for i in range(4)])
              for uid in range(3)]  # the last token back at 8.0 s
    drive = dict(tracks=tracks, t_open=0.0, t_close=close, t_end=8.5)
    assert run.end_to_end(drive)["out_tok_s"] == pytest.approx(12 / 8.5)
