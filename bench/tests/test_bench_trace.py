"""``bench/trace.py`` on a trace recorded on a TPU v5e, and on a small
hand-made event list whose answers are worked out by hand.

The recorded trace: three steps of a sampled GraphSAGE run at the
papers widths on a 16,384-node graph (b = 128), between two ``on_step``
stamps, with a ``window`` span on the host."""
import os
import shutil

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    shutil.copy(os.path.join(DATA, "v5e_small_sampled.xplane.pb"), d)
    return trace.load(str(d))


def test_recorded_trace_loads_device_ops_and_the_window(recorded):
    assert recorded["chips"] == 1
    assert [h[0] for h in recorded["host"]] == ["window"]
    kernels = [d for d in recorded["device"] if d[4]]
    # three aggregation calls a step: hops 1 -> 0 and 2 -> 1 of layer 1,
    # hop 1 -> 0 of layer 2
    assert {d[1].split(" ")[0] for d in kernels} == {
        "neighbor_agg_tiled.3", "neighbor_agg_tiled.4",
        "neighbor_agg_tiled.5"}
    assert all(d[1].endswith(" custom-call") for d in kernels)


def test_recorded_busy_time_is_the_union_of_op_intervals(recorded):
    r = trace.reduce(recorded, n_chips=1)
    (w0, w1), = [(s, e) for n, s, e in recorded["host"] if n == "window"]
    # an independent union: a 100 ns timeline of the window
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for _, _, s, e, _ in recorded["device"]:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) // 100):int(np.ceil((e - w0) / 100))] = True
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert r["busy_s"] == pytest.approx(grid.sum() * 100e-9, rel=0.02)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["kernel_calls"] == 9
    assert 0 < r["kernel_s"] < r["busy_s"]
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and gaps == sorted(gaps, key=lambda g: -g[1])
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][0].startswith("neighbor_agg_tiled.4")


def test_hand_made_trace():
    events = {
        "chips": 1,
        "host": [["window", 0, 1000], ["step", 0, 500],
                 ["batch_wait", 500, 650], ["step", 650, 1000],
                 ["setup", -500, 0]],
        "device": [[0, "neighbor_agg_tiled custom-call", 100, 300, True],
                   [0, "fusion.1 fusion", 250, 400, False],
                   [0, "fusion.2 fusion", 600, 700, False],
                   [0, "fusion.2 fusion", 950, 1100, False],   # clipped
                   [0, "fusion.3 fusion", -200, -100, False]]}  # outside
    r = trace.reduce(events, n_chips=1)
    assert r["window_s"] == 1000e-9
    # [100, 400] + [600, 700] + [950, 1000]
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["kernel_s"] == pytest.approx(200e-9) and r["kernel_calls"] == 1
    # each gap named by the innermost span over its middle: 0-100 and
    # 700-950 in a step, 400-600 (middle 500) in the batch wait
    assert r["breakdown"]["idle_gaps"] == [
        ["step", pytest.approx(250e-9)], ["batch_wait", pytest.approx(200e-9)],
        ["step", pytest.approx(100e-9)]]
    assert r["breakdown"]["device_ops"][0] == [
        "neighbor_agg_tiled custom-call", pytest.approx(200e-9)]


def test_two_chips_average_their_busy_time():
    events = {"chips": 2, "host": [["window", 0, 100]],
              "device": [[0, "a x", 0, 100, False], [1, "a x", 0, 50, False]]}
    assert trace.reduce(events, n_chips=2)["busy_s"] == pytest.approx(75e-9)


def test_op_label():
    assert trace.op_label(
        "%fusion.85 = f32[2097152,172]{1,0:T(8,128)} fusion(f32[2] %a), "
        "kind=kCustom") == "fusion.85 fusion"
    assert trace.op_label(
        "%while.3 = (s32[]{:T(128)}, f32[2,3]{1,0}) while((s32[], f32[2,3]) "
        "%tuple.29), condition=%c") == "while.3 while"
    assert trace.op_label("plain") == "plain"
