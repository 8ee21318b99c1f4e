"""The reduction from a profiler trace to numbers, on a small trace recorded
on a TPU v5e (chipbench/testdata/small.xplane.pb: three launches of one
jitted step with 2 ms of host work between them, then one of another
program) against values worked out by hand from its events, and on planes
made up here."""

import os

import pytest

from chipbench import manifest, trace

SMALL = os.path.join(manifest.HERE, "testdata", "small.xplane.pb")
# the window annotation: 45,968,546 ns for 10,313,919 ns. The device's first
# launch (44,926,589) lies before it: device and host clocks agree to about
# a millisecond, so it is clipped away and two launches remain.
WINDOW_NS = 10_313_919


@pytest.fixture(scope="module")
def small():
    return trace.reduce_trace(trace.read_planes(SMALL))


def test_reads_the_planes_with_jaxs_own_reader():
    planes = {p["name"]: p for p in trace.read_planes(SMALL)}
    dev = planes["/device:TPU:0"]
    assert {ln["name"] for ln in dev["lines"]} >= {"XLA Ops", "XLA Modules"}
    assert len(trace.device_planes(list(planes.values()))) == 1


def test_window_is_the_annotations(small):
    assert small["window_s"] == pytest.approx(WINDOW_NS * 1e-9, rel=1e-9)
    assert small["chips"] == 1


def test_busy_is_the_union_of_the_ops_inside_the_window(small):
    # two steps of (copy-start 13, copy-done 1716|1725, fusion 3118|3122)
    # and one broadcast_add_fusion of 3561 ns
    by_hand = 13 + 1716 + 3118 + 13 + 1725 + 3122 + 3561
    assert small["busy_s"] == pytest.approx(by_hand * 1e-9, rel=1e-6)
    idle = 1 - small["busy_s"] / small["window_s"]
    assert idle == pytest.approx(1 - by_hand / WINDOW_NS, rel=1e-9)


def test_time_per_op_name(small):
    ops = small["op_s"]
    assert ops["copy-done f32[512,512]"] == pytest.approx(3441e-9, rel=1e-6)
    assert ops["convolution_tanh_fusion f32[512,512]"] == pytest.approx(
        6240e-9, rel=1e-6)
    assert ops["broadcast_add_fusion f32[512,512]"] == pytest.approx(
        3561e-9, rel=1e-6)


def test_time_and_launches_per_program(small):
    assert small["module_n"] == {"jit_small_step": 2.0, "jit_other_prog": 1.0}
    assert small["module_s"]["jit_small_step"] == pytest.approx(
        (4853 + 4867) * 1e-9, rel=1e-6)
    assert small["module_s"]["jit_other_prog"] == pytest.approx(3566e-9,
                                                                rel=1e-6)


def test_idle_gaps_go_to_the_host_span_over_their_middle(small):
    gaps = dict(small["breakdown"]["idle_gaps"])
    # between the launches the host sat in cb:step (waiting for the result)
    # or in cb:host_work (asleep); by hand from the event times:
    assert gaps["in:step"] == pytest.approx((3_305_901 + 3_278_061) * 1e-9,
                                            rel=1e-6)
    assert gaps["in:host_work"] == pytest.approx(
        (1_962_956 + 1_753_727) * 1e-9, rel=1e-6)
    assert gaps["within:jit_small_step"] < 1e-7
    assert sum(gaps.values()) + small["busy_s"] == pytest.approx(
        small["window_s"], rel=1e-9)


def test_lead_gap_is_the_idle_time_before_a_launch(small):
    lead = small["module_lead_gap_s"]
    assert lead["jit_small_step"] == [pytest.approx(3_305_897e-9, rel=1e-5)]
    assert lead["jit_other_prog"] == [pytest.approx(3_278_057e-9, rel=1e-5)]


def test_breakdown_is_short_and_sorted(small):
    ops = small["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops == sorted(ops, key=lambda kv: -kv[1])
    assert ops[0][0] == "convolution_tanh_fusion f32[512,512]"


def test_host_spans_are_kept_by_name(small):
    assert len(small["span_s"]["cb:step"]) == 3
    assert len(small["span_s"]["cb:host_work"]) == 3


@pytest.mark.parametrize("raw,short", [
    ("%fusion.113 = bf16[7,7,3,64]{3,2,1,0:T(4,128)(2,1)S(1)} fusion(bf16["
     "256,112,112,64]{0,3,2,1} %fusion.357), kind=kOutput",
     "fusion.113 bf16[7,7,3,64]"),
    ("%copy-start = (f32[512,512]{1,0}, f32[512,512]{1,0}, u32[]) "
     "copy-start(f32[512,512] %a.1)", "copy-start f32[512,512]"),
    ("%all-reduce.7 = f32[64]{0} all-reduce(f32[64] %x)",
     "all-reduce.7 f32[64]"),
    ("not an hlo line", "not an hlo line"),
])
def test_op_names_are_cut_to_op_and_result(raw, short):
    assert trace.op_name(raw) == short


def test_module_name_drops_the_fingerprint():
    assert trace.module_name("jit__decode_paged(123456)") == \
        "jit__decode_paged"


def _plane(n, ops, mods=()):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Ops", "events": list(ops)},
        {"name": "XLA Modules", "events": list(mods)}]}


def test_overlapping_ops_count_once_and_chips_are_averaged():
    planes = [
        _plane(0, [("%a = f32[1] add()", 0.0, 100.0),
                   ("%b = f32[1] add()", 50.0, 100.0)],
               [("jit_f(1)", 0.0, 150.0)]),
        _plane(1, [("%a = f32[1] add()", 0.0, 50.0)],
               [("jit_f(1)", 0.0, 50.0)]),
    ]
    r = trace.reduce_trace(planes)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((150 + 50) / 2 * 1e-9)
    assert r["module_n"]["jit_f"] == 1.0


def test_ops_that_overlap_are_busy_once_and_counted_by_name_each():
    planes = [_plane(0, [
        ("%all-reduce.1 = f32[8] all-reduce(f32[8] %g)", 0.0, 100.0),
        ("%fusion.2 = f32[8] fusion()", 40.0, 100.0),
        ("%all-gather.3 = f32[8] all-gather(f32[2] %p)", 200.0, 30.0)])]
    r = trace.reduce_trace(planes)
    assert r["busy_s"] == pytest.approx((140 + 30) * 1e-9)
    assert r["window_s"] == pytest.approx(230e-9)
    assert sum(r["op_s"].values()) == pytest.approx(230e-9)


def test_a_launch_that_the_window_cuts_counts_by_its_share():
    """Steps dispatched ahead run back to back, so the window's ends cut one
    each, and the profiler records the one that ran when it started from
    there only (here 60 ns of a launch of 100). Four events in a window of
    250 ns are two and a half launches: each cut one counts by its length
    inside over the length of the whole launches."""
    step = "%fusion.1 = f32[8] fusion()"
    at = [(40.0, 60.0), (100.0, 100.0), (200.0, 100.0), (300.0, 100.0)]
    planes = [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [("cb:window", 50.0, 250.0)]}]},
        _plane(0, [(step, s, d) for s, d in at],
               [("jit_step(7)", s, d) for s, d in at])]
    r = trace.reduce_trace(planes)
    assert r["window_s"] == pytest.approx(250e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["module_s"]["jit_step"] == pytest.approx(250e-9)
    assert r["module_n"]["jit_step"] == pytest.approx(2.5)


def test_a_cut_launch_with_no_whole_one_beside_it_counts_by_its_own_share():
    planes = [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [("cb:window", 50.0, 100.0)]}]},
        _plane(0, [("%a = f32[1] add()", 0.0, 200.0)],
               [("jit_f(1)", 0.0, 200.0)])]
    assert trace.reduce_trace(planes)["module_n"]["jit_f"] == \
        pytest.approx(0.5)


def test_steps_sent_ahead_as_the_chip_recorded_them():
    """The program events of a traced run of ``resnet50-fit-staged`` on the
    chip (PR 26; nanoseconds from the window's start, the whole steps laid
    out evenly): the profiler recorded 57.9 ms of the step that ran when it
    started and 6.5 ms of the one that ran when it stopped, which ends
    inside the window. Counted whole they were 39 steps of 103.2 ms; they
    are 37.58 of 107.15 ms."""
    window, step_ns = 4027696268.0, 107150921.0
    at = [(-2391612.0, 57878722.0)]
    at += [(55495911.0 + i * 107156232.0, step_ns) for i in range(37)]
    at += [(4020296383.0, 6516155.0)]
    op = "%fusion.1 = f32[8] fusion()"
    planes = [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [("cb:window", 0.0, window)]}]},
        _plane(0, [(op, s, d) for s, d in at],
               [("jit_step(45)", s, d) for s, d in at])]
    r = trace.reduce_trace(planes)
    n = r["module_n"]["jit_step"]
    assert n == pytest.approx(37 + (57878722 - 2391612 + 6516155) / step_ns)
    assert r["busy_s"] / n * 1e3 == pytest.approx(107.15, rel=1e-4)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.reduce_trace([{"name": "/host:CPU", "lines": []}])


def test_no_trace_no_file():
    with pytest.raises(FileNotFoundError):
        trace.newest_xplane(os.path.join(manifest.HERE, "metrics"))
