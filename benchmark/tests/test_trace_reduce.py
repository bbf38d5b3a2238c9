"""The trace reduction: interval arithmetic and the reduction on events made
by hand (every expected value worked out on paper, in the comments), then the
same reduction on traces recorded on a TPU v5e (fixtures/*.xplane.pb.gz)."""

import glob
import gzip
import os
import shutil

import pytest

from benchmark import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def op(start, end, name, category=""):
    return tr.Op(start=float(start), end=float(end), name=name,
                 category=category, text=name.lower())


def test_parse_hlo_text():
    name, category, shape = tr.parse_hlo(
        "%add_add_fusion.2 = bf16[8,256,1024]{2,1,0:T(8,128)(2,1)S(1)} "
        "fusion(bf16[8,256,1024]{2,1,0:T(8,128)(2,1)S(1)} %gte.1365, "
        "u8[256,4096]{1,0:T(8,128)(4,1)S(1)} %gte.1371), kind=kOutput, "
        "calls=%fused_computation.96.clone.clone")
    assert (name, category, shape) == ("add_add_fusion.2", "fusion kOutput",
                                       "bf16[8,256,1024]")
    name, category, shape = tr.parse_hlo(
        "%while.6 = (s32[]{:T(128)}, bf16[8,256]{1,0:T(8,128)}, "
        "/*index=2*/s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.160), "
        "condition=%wide.region_5.7, body=%wide.region_0.6.sunk")
    assert (name, category) == ("while.6", "while")
    assert shape == "(s32[], bf16[8,256], /*index=2*/s32[])"
    name, category, _ = tr.parse_hlo(
        '%flash_4d_fwd.3 = (bf16[8,256,16,64]{3,2,1,0}, f32[8,16,256]{2,1,0}) '
        'custom-call(bf16[8,256,16,64]{3,2,1,0} %a), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert (name, category) == ("flash_4d_fwd.3",
                                "custom-call tpu_custom_call")
    kernel = tr.Op(0, 1, name, category, "")
    assert kernel.is_kernel and not kernel.is_matmul
    name, category, _ = tr.parse_hlo(
        "%all-gather-start.3 = (f32[4]{0}, f32[16]{0}) "
        "all-gather-start(f32[4]{0} %p), dimensions={0}")
    assert tr.Op(0, 1, name, category, "").is_collective
    name, category, _ = tr.parse_hlo(
        "%fusion.5 = f32[1296,1000]{1,0} fusion(f32[5120,1000]{1,0} %f.3), "
        "kind=kCustom, calls=%all-reduce-scatter.4")
    fused = tr.Op(0, 1, name, category, "kind=kcustom, calls=%all-reduce-scatter.4")
    assert fused.is_collective and not fused.is_matmul
    plain = tr.Op(0, 1, "fusion.9", "fusion kCustom", "calls=%fused_computation.2")
    assert not plain.is_collective
    assert tr.parse_hlo("0") == ("0", "", "")


def test_async_collective_pair_is_in_flight_from_start_to_done():
    # start 0..1, a matmul 1..9 overlaps the flight, done waits 9..12
    ops = [op(0, 1, "async-collective-start.7", "fusion kCustom"),
           op(1, 9, "fusion.1", "fusion kOutput"),
           op(9, 12, "async-collective-done.7", "fusion kCustom")]
    t = tr.reduce_events([("/device:TPU:0", ops)], [("bench/window", 0.0, 12.0)])
    running, exposed = t.collective_seconds()
    assert running == pytest.approx(12e-9)       # 0..12 in flight
    assert exposed == pytest.approx(4e-9)        # 0..1 and 9..12


def test_union_total_subtract_gaps():
    cover = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert cover == [(0, 3), (5, 8)]          # (10,10) is empty
    assert tr.total(cover) == 6
    assert tr.subtract(cover, [(2, 6)]) == [(0, 2), (6, 8)]
    assert tr.subtract(cover, []) == cover
    assert tr.subtract([(0, 10)], [(0, 1), (2, 3), (9, 12)]) == [(1, 2), (3, 9)]
    assert tr.gaps(cover, 0, 10) == [(3, 5), (8, 10)]


def hand_trace():
    # window 0..100 ns, one device.
    #   while          10..90  encloses the next four, whose union is
    #                          10..65: own time 80 - 55 = 25
    #   fusion.1       10..30  fusion kOutput (a matmul)  own 20
    #   all-gather.1   30..40  alone: exposed 10          own 10
    #   flash_4d_fwd   40..60  custom-call                own 20
    #   all-reduce.2   55..65  overlaps the kernel 55..60: exposed 60..65 = 5,
    #                          ends after the kernel, so it is NOT nested in
    #                          it: both keep their time (two engines at
    #                          once); it is nested in the while       own 10
    #   fusion.2       95..120 fusion kLoop, clipped to 95..100: own 5
    ops = [op(10, 90, "while.1", "while"),
           op(10, 30, "fusion.1", "fusion kOutput"),
           op(30, 40, "all-gather.1", "all-gather"),
           op(40, 60, "flash_4d_fwd.3", "custom-call tpu_custom_call"),
           op(55, 65, "all-reduce.2", "all-reduce"),
           op(95, 120, "fusion.2", "fusion kLoop")]
    spans = [("bench/window", 0.0, 100.0), ("bench/dispatch", 0.0, 8.0),
             ("bench/fence", 88.0, 100.0)]
    return tr.reduce_events([("/device:TPU:0", ops)], spans)


def test_reduction_by_hand():
    t = hand_trace()
    assert t.window_s == pytest.approx(100e-9)
    # busy: 10..90 and 95..100 = 85 ns; idle 15%
    assert t.busy_s() == pytest.approx(85e-9)
    assert t.idle_pct() == pytest.approx(15.0)
    cats = {k: round(v * 1e9, 6) for k, v in t.category_seconds().items()}
    assert cats == {"while": 25.0, "fusion kOutput": 20.0,
                    "all-gather": 10.0, "custom-call tpu_custom_call": 20.0,
                    "all-reduce": 10.0, "fusion kLoop": 5.0}
    # 30 of the 90 ns of own time are outside matmuls, kernels, collectives
    assert t.self_seconds(lambda o: not (o.is_matmul or o.is_kernel
                                         or o.is_collective)) \
        == pytest.approx(30e-9)
    assert t.seconds_matching("flash_") == pytest.approx(20e-9)
    assert t.count_matching("flash_") == 1
    running, exposed = t.collective_seconds()
    assert running == pytest.approx(20e-9)     # 30..40 and 55..65
    assert exposed == pytest.approx(15e-9)     # 30..40 and 60..65
    # gaps 0..10 (dispatch covers 8 of it), 90..95 and 100 (fence)
    assert t.idle_gaps() == [["bench/dispatch", pytest.approx(10e-9)],
                             ["bench/fence", pytest.approx(5e-9)]]
    top = dict((k, v) for k, v in t.top_ops())
    assert top["flash_4d_fwd"] == pytest.approx(20e-9)
    assert top["fusion [fusion kOutput]"] == pytest.approx(20e-9)


def test_no_window_span_uses_the_extent_of_the_ops():
    t = tr.reduce_events([("/device:TPU:0", [op(5, 10, "a"), op(20, 25, "b")])],
                         [])
    assert t.window == (5.0, 25.0)
    assert t.idle_pct() == pytest.approx(50.0)


def test_two_devices_average():
    t = tr.reduce_events(
        [("/device:TPU:0", [op(0, 10, "a")]), ("/device:TPU:1", [op(0, 5, "a")])],
        [("bench/window", 0.0, 10.0)])
    assert t.busy_s() == pytest.approx(7.5e-9)
    assert t.busy_s(device=1) == pytest.approx(5e-9)


def brute_force_busy_ns(ops, lo, hi):
    """Busy time by a sweep over sorted edges: independent of `union`."""
    edges = []
    for o in ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    depth, busy, last = 0, 0.0, None
    for at, step in edges:
        if depth > 0:
            busy += at - last
        depth += step
        last = at
    return busy


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.xplane.pb.gz"))) or [None])
def test_recorded_tpu_trace(path, tmp_path):
    if path is None:
        pytest.skip("no recorded fixture")
    raw = tmp_path / "fixture.xplane.pb"
    with gzip.open(path, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert os.path.getsize(raw) < 500_000
    t = tr.reduce_xplane(str(raw))
    four = "fsdp4" in os.path.basename(path)    # recorded on four chips;
    assert len(t.devices) == 1                  # the fixture keeps chip 0
    assert any(name == tr.WINDOW_SPAN for name, _, _ in t.spans)
    assert 0.0 < t.busy_s() <= t.window_s
    for dev in t.devices:
        busy = sum(b - a for a, b in dev.busy)
        assert busy == pytest.approx(
            brute_force_busy_ns(dev.ops, *t.window), rel=1e-9)
        # every busy instant is the own time of the innermost op there
        own = sum(o.self_ns for o in dev.ops)
        assert own >= busy * (1 - 1e-9)
    # the kernels are found by the name their pallas_call carries
    assert t.seconds_matching("flash_") > 0
    assert t.seconds_matching("fused_adamw") > 0
    assert any(o.is_matmul for o in t.devices[0].ops)
    running, exposed = t.collective_seconds()
    assert 0.0 <= exposed <= running
    assert (running > 0) == four
    # the window held 4 steps of a 2-block model whose compiled step has 3
    # attention kernels (forward, the forward again under remat, backward)
    # and 19 fused-optimizer kernels (`program_facts` of the same program)
    steps = round(t.count_matching("fused_adamw") / 19)
    assert t.count_matching("fused_adamw") == 19 * steps
    assert t.count_matching("flash_") == 3 * 2 * steps
    if four:                # pinned from the recording (PR 22, four chips)
        assert steps == 3
        assert running == pytest.approx(10.607841e-3, rel=1e-6)
        assert exposed == pytest.approx(3.983708e-3, rel=1e-6)
    else:                   # pinned from the recording (PR 22, one chip)
        assert steps == 4
        assert t.window_s == pytest.approx(21.91106e-3, rel=1e-6)
        assert t.busy_s() == pytest.approx(19.781901e-3, rel=1e-6)
        assert t.idle_pct() == pytest.approx(9.71728, rel=1e-5)
        assert t.seconds_matching("flash_") == pytest.approx(1.305713e-3,
                                                             rel=1e-6)
        assert t.category_seconds()["fusion kOutput"] == pytest.approx(
            12.310055e-3, rel=1e-6)
        assert t.idle_gaps()[0] == ["bench/fence",
                                    pytest.approx(2.129093e-3, rel=1e-5)]
