"""The readers that lean on the program's own names: the three kernel
groups (hand counts of their required operations, and their seconds on a
recorded trace of the chip with the new names) and the two log-boundary
readers (on spans made by hand)."""

import os
import types

import pytest

from benchmark import boundary_spans as bs
from benchmark import flops, harness, kernel_parts, peaks, trace_reduce as tr
from benchmark.layers import (flash_bwd_roofline_pct, flash_fwd_roofline_pct,
                              xent_roofline_pct)
from benchmark.tests.conftest import ROOT

HERE = os.path.dirname(__file__)
# 0.183 s of gpt2m-pretrain-1k on one v5e chip (my chip run, PR 23), inside the
# accumulation scan: one micro-batch from its fused head through its backward,
# then the next one's forward (one call of each kernel per layer), the while clipped,
# every kernel under its own name.
RECORDED = os.path.join(HERE, "recorded_trace_gpt2m_named.json.gz")
V5E = peaks.peaks_for("TPU v5 lite")


def _cell(name):
    return harness.load_cell(name, ROOT)


# ------------------------------------------------------------ required work

def test_parts_by_hand_gpt2m_pretrain_1k():
    """8 sequences a call x 8 calls, 16 heads of 64 at 1,024 positions, 24
    layers, causal: one score-sized product is 2 x 8 x 16 x 1024^2 x 64 / 2
    = 8,589,934,592 operations, one tensor 8 x 1024 x 1024 x 2 bytes."""
    parts = kernel_parts.parts(_cell("gpt2m-pretrain-1k"))
    product, tensor, n = 8_589_934_592, 16_777_216, 24 * 8
    assert parts["flash_fwd"].flops == pytest.approx(2 * product * n)
    assert parts["flash_bwd"].flops == pytest.approx(5 * product * n)
    assert parts["flash_fwd"].hbm_bytes == pytest.approx(4 * tensor * n)
    assert parts["flash_bwd"].hbm_bytes == pytest.approx(8 * tensor * n)
    logits = 2 * 8192 * 1024 * 50257             # rows x d_model x vocab
    assert parts["xent"].flops == pytest.approx(4 * logits * 8)
    assert all(p.bound(V5E) == "compute" for p in parts.values())


@pytest.mark.parametrize("name", ["gpt2m-pretrain-1k", "gpt2m-dp4-sync"])
def test_parts_sum_to_the_jobs_kernel_cost_per_step(name):
    """What the three readers divide up is what ``pallas_roofline_pct`` reads
    whole: the family's ``kernel_cost_per_step``."""
    cell = _cell(name)
    family = cell.load_module("families", cell.config["family"])
    t = cell.traffic
    built = family.build(cell.config, dict(t, pool_batches=1), 0,
                         t["micro_batch"] * t["accumulation"] * cell.chips,
                         abstract=True)
    parts = kernel_parts.parts(cell)
    whole = parts["flash_fwd"] + parts["flash_bwd"] + parts["xent"]
    assert whole.flops == pytest.approx(built.kernel_cost_per_step.flops)
    assert whole.hbm_bytes == pytest.approx(built.kernel_cost_per_step.hbm_bytes)


def test_no_kernel_no_parts():
    assert kernel_parts.parts(_cell("bertl-replica-b32")) is None


# ------------------------------------------------------- the recorded trace

def _record(trace, steps=1.0, cell="gpt2m-pretrain-1k"):
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": _cell(cell)}


def test_recorded_trace_names_every_kernel_and_groups_sum_to_pallas_s():
    s = tr.summarize(tr.load_json(RECORDED))
    d = s.devices[0]
    groups = {g: v for g, v in d.by_group.items() if g.startswith("pallas:")}
    assert set(groups) == {"pallas:" + n for n in
                           kernel_parts.FLASH_FWD + kernel_parts.FLASH_BWD
                           + kernel_parts.XENT}
    named = sum(kernel_parts.group_seconds(s, names) for names in
                (kernel_parts.FLASH_FWD, kernel_parts.FLASH_BWD,
                 kernel_parts.XENT))
    assert named == pytest.approx(d.pallas_s, rel=1e-9)
    assert d.pallas_s > 0.05                     # seconds, of a 0.2 s window


def test_readers_on_the_recorded_trace():
    """The three shares against the seconds counted by hand from the
    recorded operations (``steps`` is 1/8: the window holds one call of each
    kernel per layer, an eighth of a step's)."""
    trace = tr.load_json(RECORDED)
    ops = [op for op in trace.devices[0] if tr.is_pallas(op)]
    lo, hi = trace.window()
    by_hand = {}
    for op in ops:
        if op.start >= lo and op.end <= hi:
            base = op.name.rsplit(".", 1)[0]
            by_hand[base] = by_hand.get(base, 0.0) + op.dur
    record = _record(tr.summarize(trace), steps=1 / 8)
    parts = kernel_parts.parts(record["cell"])
    expect = {
        flash_fwd_roofline_pct: parts["flash_fwd"].least_seconds(V5E) / 8
        / by_hand["flash_fwd"],
        flash_bwd_roofline_pct: parts["flash_bwd"].least_seconds(V5E) / 8
        / (by_hand["flash_bwd_dkv"] + by_hand["flash_bwd_dq"]),
        xent_roofline_pct: parts["xent"].least_seconds(V5E) / 8
        / (by_hand["xent_fwd"] + by_hand["xent_bwd_dh"]
           + by_hand["xent_bwd_dw"]),
    }
    for reader, share in expect.items():
        assert reader.read(record) == pytest.approx(100 * share, rel=1e-6)
        assert 0 < reader.read(record) < 100


def test_nothing_to_read_is_none():
    s = tr.summarize(tr.load_json(RECORDED))
    for reader in (flash_fwd_roofline_pct, flash_bwd_roofline_pct,
                   xent_roofline_pct):
        assert reader.read(dict(_record(s), trace=None)) is None   # CPU rehearsal
        assert reader.read(_record(s, cell="bertl-pretrain-128")) is None


def test_a_trace_with_the_old_names_fails_the_run(monkeypatch):
    """The program names its kernels; a trace whose Mosaic time sits under
    other names (a stale compile cache, a lost scope) raises."""
    old = tr.summarize(tr.load_json(
        os.path.join(HERE, "recorded_trace_gpt2m.json.gz")))
    assert "pallas:attn" in old.devices[0].by_group
    with pytest.raises(harness.BenchmarkError, match="pallas:attn"):
        flash_fwd_roofline_pct.read(_record(old))
    # A program older than the names gives the reader nothing to read.
    monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: None)
    assert flash_fwd_roofline_pct.read(_record(old)) is None


# ----------------------------------------------------------- log boundaries

MS = 1_000_000     # nanoseconds


def _span(name, t0_ms, dur_ms):
    return (name, 1, int(t0_ms * MS), int(dur_ms * MS), None)


def _loop(boundaries, planes_ms=4.0, callback_ms=1.0, own_ms=0.5,
          feed_ms=2.0, dispatch_ms=3.0, step_ms=100.0):
    """A per-step loop with ``log_every`` 1 as its spans record it."""
    spans, t = [], 0.0
    for _ in range(boundaries):
        spans.append(_span("runner.run.dispatch", t, dispatch_ms))
        t += dispatch_ms
        spans.append(_span("train.readback_wait", t, step_ms))
        t += step_ms
        b0 = t
        t += own_ms / 2
        spans.append(_span("train.boundary.planes", t, planes_ms))
        t += planes_ms + own_ms / 2
        spans.append(_span("train.boundary.on_metrics", t, callback_ms))
        t += callback_ms
        spans.append(_span("train.boundary", b0, t - b0))
        t += feed_ms
    spans.append(_span("runner.run.dispatch", t, dispatch_ms))
    return spans


def test_gap_runs_from_readback_end_to_next_dispatch_end():
    gaps = bs.gaps_ms(_loop(5))
    # boundary block 0.5 + 4 + 1, the feed 2, the dispatch 3; first dropped
    assert gaps == pytest.approx([10.5] * 4)
    assert bs.median(gaps) == pytest.approx(10.5)


def test_boundary_self_is_the_span_less_its_two_children():
    parts = bs.boundary_parts_ms(_loop(3))
    assert len(parts) == 3
    for own, planes, callback in parts:
        assert (own, planes, callback) == pytest.approx((0.5, 4.0, 1.0))


def test_only_boundaries_before_the_profiler_came_on_count():
    spans = _loop(6)
    # on_metrics of the 4th boundary starts the profiler (and takes 300 ms)
    fourth = [s for s in spans if s[0] == "train.boundary.on_metrics"][3]
    boundaries = [(i + 1, 0.0, 1.0, "armed") for i in range(3)] + [
        (4, fourth[2] * 1e-9, 1.0, "armed"), (5, 9.9, 1.0, "on"),
        (6, 9.99, 1.0, "done")]
    cutoff = bs.profiler_on_ns(boundaries)
    assert cutoff == pytest.approx(fourth[2])
    assert len(bs.gaps_ms(spans, cutoff)) == 2      # boundaries 2 and 3
    assert len(bs.boundary_parts_ms(spans, cutoff)) == 3
    # an untraced run never switches: every boundary counts
    assert bs.profiler_on_ns([(1, 0.0, 1.0, "off"), (2, 1.0, 1.0, "off")]) \
        == float("inf")


def test_a_program_without_the_spans_gives_nothing():
    old = [s for s in _loop(4) if not s[0].startswith("train.boundary")]
    assert bs.boundary_parts_ms(old) == [] and bs.median([]) is None
    assert bs.gaps_ms(old) == pytest.approx([10.5] * 3)   # it had these two


def test_boundary_readers_read_the_programs_ring(monkeypatch):
    from benchmark.layers import boundary_gap_ms, boundary_self_ms
    monkeypatch.setattr(bs, "program_spans", lambda: _loop(4))
    record = {"boundaries": [(i, float(i), 1.0, "off") for i in range(4)]}
    assert boundary_gap_ms.read(record) == pytest.approx(10.5)
    assert boundary_self_ms.read(record) == pytest.approx(0.5)
    monkeypatch.setattr(bs, "program_spans", lambda: [])
    assert boundary_gap_ms.read(record) is None
    assert boundary_self_ms.read(record) is None


def test_counter_readers_give_nothing_for_a_counter_never_booked():
    from benchmark import program_counters
    from benchmark.layers import jit_backend_s, jit_trace_lower_s, state_place_s
    assert program_counters.value("no.such_counter") is None
    from autodist_tpu import telemetry
    if not telemetry.registry().get("jit.programs"):
        assert jit_backend_s.read({}) is None
        assert jit_trace_lower_s.read({}) is None
    if not telemetry.registry().get("setup.state_place_calls"):
        assert state_place_s.read({}) is None
    telemetry.counter("setup.state_place_calls").inc(2)
    telemetry.counter("setup.state_place_s").inc(1.5)
    assert state_place_s.read({}) >= 1.5
