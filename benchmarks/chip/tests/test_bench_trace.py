"""The trace reduction, against counts written by hand."""
import os

import bench_paths  # noqa: F401
import trace_reduce as tr

# device ops (name, start, duration) in ns; window [100, 1100)
OPS = [("a", 50, 100),   # 100..150 inside
       ("b", 140, 60),   # 140..200, overlaps a
       ("a", 400, 100),  # 400..500
       ("k", 450, 20),   # nested inside the previous a
       ("c", 1050, 200)]  # 1050..1100 inside
HOST = [("pump_step", 90, 150),      # 90..240
        ("bookkeeping", 240, 100),   # 240..340
        ("wait_arrival", 340, 400),  # 340..740
        ("submit", 900, 50)]         # 900..950


def test_busy_union_and_idle_share():
    # union: [100,200) + [400,500) + [1050,1100) = 100 + 100 + 50
    assert tr.busy(OPS, 100, 1100) == 250
    s = tr.summary(dict(devices={"/device:TPU:0": dict(ops=OPS, modules=[])},
                        host=HOST, window=(100, 1100)))
    assert s["busy_s"] == 250e-9 and s["window_s"] == 1000e-9


def test_op_sums_clip_to_window():
    assert tr.op_sums(OPS, 100, 1100) == {"a": 150, "b": 60, "k": 20, "c": 50}


def test_idle_gaps_and_attribution():
    gaps = tr.idle_gaps(OPS, 100, 1100)
    assert gaps == [(200, 400), (500, 1050)]
    # 200..240 pump_step, 240..340 bookkeeping, 340..400 + 500..740 wait,
    # 900..950 submit, the rest of 500..1050 no span
    assert tr.attribute(gaps, HOST) == {"pump_step": 40, "bookkeeping": 100,
                                        "wait_arrival": 300, "submit": 50,
                                        "unattributed": 260}


def test_top_is_sorted_seconds():
    assert tr.top({"x": 1, "y": 3, "z": 2}, 2) == [["y", 3e-9], ["z", 2e-9]]


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_recorded_tpu_trace():
    """A v5e trace (make_trace_fixture.py): one jitted program run three
    times, the first launched just before the window opened. Counts read by
    hand off its events: each run is a copy-start, a copy-done and two
    fusions; the device clock sits about 0.23 ms before the host's."""
    t = tr.load(FIXTURE)
    assert list(t["devices"]) == ["/device:TPU:0"]
    assert [h[0] for h in t["host"]] == [
        "pump_step", "wait_arrival", "submit", "wait_arrival", "pump_step", "wait_arrival"]
    ws, we = t["window"]
    assert we - ws == 19_797_519
    ops = t["devices"]["/device:TPU:0"]["ops"]
    assert len(ops) == 12
    # runs 2 and 3 lie inside the window: 13 + 5 + 89713 + 91457 and
    # 13 + 3 + 89713 + 91410 ns; run 1 ended before it opened
    assert tr.busy(ops, ws, we) == 181_188 + 181_139
    sums = {tr.short(k): v for k, v in tr.op_sums(ops, ws, we).items()}
    assert sums == {"%copy-start": 26, "%copy-done": 8,
                    "%convolution_tanh_fusion": 179_426, "%fusion": 182_867}
    s = tr.summary(t)
    assert s["window_s"] == 19_797_519 / 1e9 and s["busy_s"] == 362_327 / 1e9
    idle = dict((k, round(v * 1e9)) for k, v in s["idle_gaps"])
    assert idle == {"wait_arrival": 16_355_713, "pump_step": 2_086_961,
                    "submit": 953_449, "unattributed": 39_069}
    assert sum(idle.values()) == (we - ws) - 362_327


def _with_plane_of_device_1(tmp_path, shift_ns):
    """The fixture with one more plane: a copy of ``/device:TPU:0`` named
    ``/device:TPU:1``, every line of it ``shift_ns`` later. XSpace field 1
    holds each XPlane, whose field 2 is its name and field 3 its lines; an
    XLine's field 3 is the time in ns its events are offset from, which the
    fixture's lines leave at 0 (absent). Protobuf merges a message appended
    to another, so a field appended sets it, and the copy is the file with
    the new plane appended."""
    import make_engine_trace_fixture as pb

    def shifted(line):
        return line + pb._varint_bytes(3 << 3) + pb._varint_bytes(shift_ns)

    with open(FIXTURE, "rb") as f:
        space = f.read()
    plane = next(v for f, v, _ in pb._fields(space)
                 if f == 1 and any(g == 2 and w == b"/device:TPU:0" for g, w, _ in pb._fields(v)))
    copy = b"".join(pb._field(2, b"/device:TPU:1") if f == 2 else
                    pb._field(3, shifted(v)) if f == 3 else raw
                    for f, v, raw in pb._fields(plane))
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(space + pb._field(1, copy))
    return str(path)


def test_only_the_cell_planes_are_read(tmp_path):
    """A one-chip cell on a host of several chips reads its chip's plane
    alone. The extra plane is device 0's a millisecond later: its first run
    then lies in the window too, three runs busy where device 0 has two."""
    path = _with_plane_of_device_1(tmp_path, 1_000_000)
    both = tr.load(path)
    assert sorted(both["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    ws, we = both["window"]
    # run 1 of the copy: 13 + 3 + 89714 + 91449 ns, now inside the window
    assert tr.busy(both["devices"]["/device:TPU:1"]["ops"], ws, we) == 362_327 + 181_179
    assert tr.summary(both)["busy_s"] == (362_327 + 362_327 + 181_179) / 2 / 1e9
    # kept to device 0: every reading of test_recorded_tpu_trace, unchanged
    alone, plain = tr.load(path, [0]), tr.load(FIXTURE)
    assert alone == plain and list(alone["devices"]) == ["/device:TPU:0"]
    assert tr.summary(alone) == tr.summary(plain)
    assert tr.summary(alone)["busy_s"] == 362_327 / 1e9
    assert list(tr.load(path, [1])["devices"]) == ["/device:TPU:1"]
