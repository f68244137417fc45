"""The reduction from device trace to numbers, on intervals made by hand
and on a small recorded trace of the chip (``recorded_trace.json.gz``)."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Op

HERE = os.path.dirname(__file__)
# 0.12 s of gpt2m-pretrain-1k on one v5e chip (my chip run, PR 22): one
# stretch inside the accumulation scan, the enclosing ``while`` clipped to it.
RECORDED_GPT = os.path.join(HERE, "recorded_trace_gpt2m.json.gz")
# 0.1 s of gpt2m-dp4-sync on chips 0 and 3 of the four (my chip run, PR 22):
# the stretch of one step's backward pass that holds its nine all-reduces.
RECORDED_DP4 = os.path.join(HERE, "recorded_trace_dp4.json.gz")
EXPECT_DP4 = {"coll0": 0.019513938, "coll3": 0.019516872, "busy0": 0.099715512}


def _raster_busy(ops, window, resolution=1e-8):
    """Busy time the slow way, for comparison: mark every 10 ns cell in
    which an operation other than control flow runs."""
    import numpy as np
    n = int(round((window[1] - window[0]) / resolution))
    edges = np.zeros(n + 1, np.int32)
    for op in ops:
        if tr.is_control_flow(op):
            continue
        a = int(round((op.start - window[0]) / resolution))
        b = int(round((op.end - window[0]) / resolution))
        if b > a:
            edges[max(a, 0)] += 1
            edges[min(b, n)] -= 1
    return float((np.cumsum(edges)[:-1] > 0).sum() * resolution)


def test_recorded_one_chip_trace():
    trace = tr.load_json(RECORDED_GPT)
    ops = trace.devices[0]
    assert len(ops) == 6088
    assert sum(tr.is_pallas(op) for op in ops) == 53
    assert sum(tr.is_control_flow(op) for op in ops) == 1
    s = tr.summarize(trace)
    d = s.devices[0]
    assert s.window_s == pytest.approx(0.12)
    assert d.busy_s == pytest.approx(_raster_busy(ops, s.window), abs=2e-6)
    assert d.busy_s == pytest.approx(0.119382482, abs=1e-8)
    assert 100 * (1 - s.busy_s / s.window_s) == pytest.approx(0.5146, abs=1e-3)
    # the kernels: the sum of the Mosaic custom calls' own durations
    assert d.pallas_s == pytest.approx(
        sum(op.dur for op in ops if tr.is_pallas(op)))
    assert d.pallas_s == pytest.approx(0.048993545, abs=1e-8)
    assert d.collective_s == 0.0 and d.exposed_collective_s == 0.0
    top = s.top_ops(3)
    assert [name for name, _ in top] == [
        "pallas:attn", "select_add_fusion (kOutput)", "fusion (kOutput)"]
    assert top[0][1] == pytest.approx(0.039951512, abs=1e-8)
    # self times add up to the busy time: nothing overlaps on one chip's
    # operation line but the while that holds everything
    assert sum(d.by_group.values()) == pytest.approx(d.busy_s, rel=1e-3)
    assert tr.total(d.idle) == pytest.approx(s.window_s - d.busy_s)


def test_merge_total_clip_subtract_gaps():
    merged = tr.merge([(5, 6), (0, 2), (1, 3), (3, 3), (2.5, 2.75)])
    assert merged == [(0, 3), (5, 6)]
    assert tr.total(merged) == 4
    assert tr.clip(merged, (1, 5.5)) == [(1, 3), (5, 5.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.gaps([(1, 2), (4, 6)], (0, 7)) == [(0, 1), (2, 4), (6, 7)]


def test_self_time_takes_nested_operations_out():
    ops = [Op("while.1", 0.0, 10.0, "while"), Op("fusion.1", 1.0, 2.0),
           Op("attn.3", 4.0, 3.0, tr.PALLAS), Op("fusion.2", 11.0, 1.0)]
    selfs = {op.name: s for op, s in tr.self_seconds(ops)}
    assert selfs == {"while.1": 5.0, "fusion.1": 2.0, "attn.3": 3.0,
                     "fusion.2": 1.0}


def test_summary_of_a_trace_made_by_hand():
    # window 0..20. A while of 0..10 holds a fusion (1..3) and a kernel
    # (4..7); an all-reduce 8..12 overlaps a fusion 9..10; fusion 15..16.
    ops = [Op("while.1", 0.0, 10.0, "while"),
           Op("convolution_add_fusion.1", 1.0, 2.0, "fusion:kOutput"),
           Op("attn.3", 4.0, 3.0, tr.PALLAS),
           Op("all-reduce.7", 8.0, 4.0, "all-reduce"),
           Op("fusion.9", 9.0, 1.0, "fusion:kLoop"),
           Op("fusion.2", 15.0, 1.0, "fusion:kLoop")]
    trace = tr.Trace(devices={0: ops},
                     marks={"bench.window_begin": 0.0, "bench.window_end": 20.0})
    s = tr.summarize(trace)
    d = s.devices[0]
    assert s.window_s == 20.0
    # busy: leaves only (the while is sequencing): 1..3, 4..7, 8..12, 15..16
    assert d.busy_s == pytest.approx(2 + 3 + 4 + 1)
    assert d.collective_s == pytest.approx(4.0)
    assert d.exposed_collective_s == pytest.approx(3.0)   # 8..9 and 10..12
    assert d.pallas_s == pytest.approx(3.0)
    assert d.by_group == {"convolution_add_fusion (kOutput)": 2.0,
                          "pallas:attn": 3.0, "all-reduce": 3.0,   # less fusion.9 inside it
                          "fusion (kLoop)": 2.0}
    assert d.idle[0] == (16.0, 20.0)                       # longest first
    gaps = tr.attribute_gaps(d.idle, [("train.readback_wait", 15.5, 19.0),
                                      ("train.dispatch", 12.0, 14.9)])
    # gaps: 0..1, 3..4, 7..8 under no span; 12..15 mostly under the dispatch
    # span; 16..20 mostly under the read-back
    assert gaps[0] == ["train.readback_wait", 4.0]
    assert dict(gaps) == {"train.readback_wait": 4.0, "train.dispatch": 3.0,
                          "no span": 3.0}


# Event names as the chip's trace printed them (my chip run, PR 22), cut short.
HLO = {
    "while": "%while.4 = (s32[]{:T(128)}, f32[1024,16,64]{0,2,1:T(8,128)}, "
             "f32[16,64,1024]{2,1,0:T(8,128)}) while((s32[]{:T(128)}, "
             "f32[1024,16,64]{0,2,1:T(8,128)}) %tuple.1), condition=%cond, body=%body",
    "flash": "%attn.598 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, "
             "f32[128,2,512]{2,1,0:T(2,128)}) custom-call(s32[2]{0:T(128)S(1)} "
             "%copy-done.895, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.7837), "
             'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[2]{0}}',
    "alloc": '%custom-call.251 = f32[8]{0:T(128)} custom-call(), '
             'custom_call_target="AllocateBuffer"',
    "matmul": "%fusion.6825 = f32[50257,1024]{1,0:T(8,128)} fusion(f32[50257,1024]"
              "{1,0:T(8,128)} %add_add_fusion.2, bf16[8192,1024]{1,0} %x), "
              "kind=kOutput, calls=%fused_computation.6093",
    "allreduce": "%all-reduce-start.3 = f32[1024,4096]{1,0:T(8,128)} "
                 "all-reduce-start(f32[1024,4096]{1,0:T(8,128)} %fusion.12), "
                 "channel_id=7, replica_groups={{0,1,2,3}}, to_apply=%add",
    "copydone": "%copy-done.895 = s32[2]{0:T(128)S(1)} copy-done((s32[2]{0:T(128)S(1)}, "
                "s32[2]{0:T(128)}, u32[]{:S(2)}) %copy-start.895)",
}


def test_parse_hlo_and_classification():
    parsed = {k: tr.parse_hlo(v) for k, v in HLO.items()}
    assert parsed == {
        "while": ("while.4", "while"),
        "flash": ("attn.598", "custom-call:tpu_custom_call"),
        "alloc": ("custom-call.251", "custom-call:AllocateBuffer"),
        "matmul": ("fusion.6825", "fusion:kOutput"),
        "allreduce": ("all-reduce-start.3", "all-reduce-start"),
        "copydone": ("copy-done.895", "copy-done")}
    ops = {k: Op(name, 0.0, 1.0, cat) for k, (name, cat) in parsed.items()}
    assert tr.is_control_flow(ops["while"]) and not tr.is_control_flow(ops["matmul"])
    assert tr.is_pallas(ops["flash"]) and not tr.is_pallas(ops["alloc"])
    assert tr.is_collective(ops["allreduce"]) and not tr.is_collective(ops["copydone"])
    assert tr.group(ops["flash"]) == "pallas:attn"
    assert tr.group(ops["matmul"]) == "fusion (kOutput)"
    assert tr.group(ops["allreduce"]) == "all-reduce-start"
    assert tr.parse_hlo("a name with no instruction") == \
        ("a name with no instruction", "")


def test_json_round_trip(tmp_path):
    trace = tr.Trace(devices={0: [Op("fusion.1", 0.5, 0.25, "fusion:kLoop")],
                              3: []}, marks={"bench.window_begin": 0.1})
    path = str(tmp_path / "t.json.gz")
    tr.save_json(trace, path)
    assert tr.load_json(path) == trace


def test_an_empty_trace_is_refused():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(devices={}, marks={}))
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(devices={0: []}, marks={}))


def test_read_xplane_finds_the_marks(tmp_path):
    """The reader against a trace written here: the CPU backend has no
    device plane, the host-side marks are there."""
    import glob

    import jax
    import jax.numpy as jnp
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window_begin"):
        pass
    jnp.ones((8, 8)).sum().block_until_ready()
    with jax.profiler.TraceAnnotation("bench.window_end"):
        pass
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    trace = tr.read_xplane(path)
    assert trace.devices == {}
    assert trace.marks["bench.window_end"] > trace.marks["bench.window_begin"]


def test_recorded_four_chip_trace_collectives():
    trace = tr.load_json(RECORDED_DP4)
    assert sorted(trace.devices) == [0, 3]
    s = tr.summarize(trace)
    assert s.window_s == pytest.approx(0.1)
    for d, ops in trace.devices.items():
        colls = [op for op in ops if tr.is_collective(op)]
        assert len(colls) == 9 and {op.category for op in colls} == {"all-reduce"}
        dev = s.devices[d]
        assert dev.busy_s == pytest.approx(_raster_busy(ops, s.window), abs=1e-5)  # 10 ns cells x 5,699 operations
        # one chip runs one operation at a time, so a synchronous all-reduce
        # is exposed in full: union == sum of durations == exposed part
        assert dev.collective_s == pytest.approx(sum(op.dur for op in colls))
        assert dev.exposed_collective_s == pytest.approx(dev.collective_s)
        assert dev.pallas_s > 0
    assert s.devices[0].collective_s == pytest.approx(EXPECT_DP4["coll0"], abs=1e-8)
    assert s.devices[3].collective_s == pytest.approx(EXPECT_DP4["coll3"], abs=1e-8)
    assert s.mean("collective_s") == pytest.approx(
        (EXPECT_DP4["coll0"] + EXPECT_DP4["coll3"]) / 2)
    assert s.devices[0].busy_s == pytest.approx(EXPECT_DP4["busy0"], abs=1e-8)
    assert "all-reduce" in dict(s.top_ops(10)) and "psum" in dict(s.top_ops(20))
