"""The trace reduction: on a hand-made trace whose answers can be worked out
on paper, and on a recorded, trimmed ``xplane.pb`` of a few steps of
``resnet50.fit.imagenet`` on the chip against a by-hand reading of it."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONV = "%convolution_add_fusion.3 = bf16[2,4]{1,0} fusion(bf16[2,4]{1,0} %p)"
ELTW = "%multiply_add_fusion.8 = f32[8]{0} fusion(f32[8]{0} %p0)"
ALLR = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
DOT = "%dot.4 = f32[2,2]{1,0} dot(f32[2,3]{1,0} %a, f32[3,2]{1,0} %b)"


def test_op_label_and_class():
    assert trace.op_label(CONV) == "convolution_add_fusion.3"
    assert trace.op_class(CONV, "convolution fusion") == "matmul"
    assert trace.op_class(CONV) == "other"      # the name alone does not say
    assert trace.op_class(ELTW, "loop fusion") == "other"
    assert trace.op_class(DOT) == "matmul"
    assert trace.op_class(ELTW) == "other"
    assert trace.op_class(ALLR) == "collective"
    assert trace.op_class("%all-reduce-start.2 = f32[8]{0} "
                          "all-reduce-start(f32[8]{0} %x)") == "collective"
    assert trace.op_class("%copy.3 = f32[8]{0} copy(f32[8]{0} %x)") == "other"


def hand_made():
    # times in ns. Device 0: two steps of the program "jit_step", 100 long,
    # starting at 0 and at 150; in each a conv of 60, an elementwise op of 20
    # and an all-reduce of 20 of which 10 overlap nothing. Between the steps
    # 50 of idle, under the host's annotation "bench:fit_call".
    def step(t):
        return [(t, t + 60, CONV, "matmul"), (t + 60, t + 80, ELTW, "other"),
                (t + 80, t + 100, ALLR, "collective")]
    d0 = trace.DeviceTrace(0, step(0) + step(150),
                           [(0, 100, "jit_step(1)"), (150, 250, "jit_step(1)"),
                            (120, 121, "jit_convert(2)")])
    # device 1: the same but its second step starts 20 later (more idle)
    d1 = trace.DeviceTrace(1, step(0) + step(170),
                           [(0, 100, "jit_step(1)"), (170, 270, "jit_step(1)")])
    notes = [(0, 300, "bench:window"), (90, 200, "bench:fit_call")]
    return [d0, d1], notes


def test_reduction_of_a_hand_made_trace():
    devices, notes = hand_made()
    r = trace.reduce_devices(devices, notes, chips=2)
    assert r.chips == 2
    assert r.window_s == pytest.approx(270e-9)          # 0 .. 270
    assert r.busy_s == pytest.approx(200e-9)            # both chips busy 200
    assert r.busy_s_least == pytest.approx(200e-9)
    assert r.step_program == "jit_step(1)" and r.steps == 2
    assert r.step_intervals_s == pytest.approx([150e-9])
    assert r.matmul_s == pytest.approx(120e-9)
    assert r.collective_s == pytest.approx(40e-9)
    # no other op runs while the all-reduce does: all of it is exposed
    assert r.collective_exposed_s == pytest.approx(40e-9)
    assert r.op_seconds[0] == ("convolution_add_fusion.3",
                               pytest.approx(120e-9))
    assert r.idle_gaps[0] == ("fit_call:between_steps", pytest.approx(50e-9))
    b = r.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_collective_hidden_behind_compute_is_not_exposed():
    ops = [(0, 100, CONV, "matmul"), (20, 60, ALLR, "collective")]
    r = trace.reduce_devices([trace.DeviceTrace(0, ops, [(0, 100, "jit_s")])],
                             [], chips=1)
    assert r.collective_s == pytest.approx(40e-9)
    assert r.collective_exposed_s == pytest.approx(0.0)
    assert r.busy_s == pytest.approx(100e-9)


def test_a_trace_with_no_device_plane_gives_nothing():
    assert trace.reduce_devices([], [], chips=1) is None
    assert trace.reduce_devices([trace.DeviceTrace(0, [], [])], [], 1) is None


# --- a recorded trace ---------------------------------------------------------
# tests/data/resnet50_fit_3steps.xplane.pb: three train steps of
# resnet50.fit.imagenet on one TPU v5e chip (my chip run, PR 32), recorded by
# tools/chip_readings.py --trace-out and cut down by tools/trim_trace.py to the
# device plane's "XLA Modules" and "XLA Ops" lines and the benchmark's host
# annotations. Read by hand with another parser (TensorFlow's xplane_pb2):
#   planes: /device:TPU:0 (lines XLA Modules: 6 events, XLA Ops: 10716),
#           /host:CPU (bench:traced_epochs, as the span was named then, and
#           bench:fit_call, both around all three steps)
#   step program: jit__train_step(10227421508577976820), 3 executions starting
#           at 6652048654422, 6752065249578, 6852069079422 ps (the other
#           module, jit_convert_element_type, takes 0.6 us a time)
#   XLA Ops: durations sum to 299948038526 ps, first start to last end
#           299998690078 ps; by hlo_category: "convolution fusion" 483 events
#           239671916714 ps, "loop fusion" 807 / 40912915546, "copy-done"
#           3867 / 8428490462, no collective
RECORDED = os.path.join(DATA, "resnet50_fit_3steps.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_xplane(RECORDED, chips=1)


def test_recorded_trace_busy_idle_and_window(recorded):
    assert recorded.chips == 1
    assert recorded.window_s == pytest.approx(0.299998690078, rel=1e-9)
    assert recorded.busy_s == pytest.approx(0.299948038526, rel=1e-6)
    assert recorded.busy_s_least == pytest.approx(recorded.busy_s)
    idle = 1 - recorded.busy_s_least / recorded.window_s
    assert idle == pytest.approx(1.688e-4, rel=0.01)


def test_recorded_trace_steps_and_their_intervals(recorded):
    assert recorded.step_program == "jit__train_step(10227421508577976820)"
    assert recorded.steps == 3
    assert recorded.step_intervals_s == pytest.approx(
        [0.100016595156, 0.100003829844], rel=1e-9)


def test_recorded_trace_convolution_time_covers_forward_and_backward(recorded):
    # ResNet-50 has 53 convolutions and a dense head: 54 forward products and
    # 2 x 54 - 1 backward ones (no gradient of the image) = 161 a step
    assert recorded.matmul_s == pytest.approx(0.239671916714, rel=1e-9)
    devices, _ = trace.read_planes(RECORDED)
    assert sum(op[3] == "matmul" for op in devices[0].ops) == 3 * 161
    assert recorded.collective_s == 0.0
    assert recorded.op_seconds[0][0].startswith(("fusion.", "convert_reduce"))


def test_recorded_trace_share_of_the_roofline_is_under_100(recorded):
    import json
    from harness import work
    with open(os.path.join(os.path.dirname(DATA), "..", "configs",
                           "resnet50_imagenet.json")) as f:
        layers = json.load(f)["layers"]
    least = work.min_step_seconds(layers, 256, 2,
                                  work.load_peaks("TPU v5 lite"))["seconds"]
    share = least / (recorded.matmul_s / recorded.steps)
    assert share == pytest.approx(0.6246, rel=0.01)
