"""The trace reduction on a small synthetic trace, worked by hand.

Two chips.  Times in milliseconds below, seconds in the code.  Three
executions of program ``step`` start at 0, 10 and 20 on chip 0 (1, 11, 21
on chip 1); with ``skip=0`` the steady window is two steps: [0, 20] and
[1, 21].

Chip 0, per step (offsets from the step's start):
    fusion.1        0.0 - 4.0
    all-gather.1    3.0 - 6.0   overlaps fusion.1 for 1.0, alone for 2.0
    flash_fwd       6.0 - 8.0
    while.1         8.5 - 9.5   encloses fusion.2 8.5 - 9.0, fusion.3 9.0 - 9.5
                                (control flow: left out by its opcode)
  busy union a step: [0, 8] and [8.5, 9.5] = 9.0; idle 1.0 in gaps of 0.5
  (8.0 - 8.5, under a host span) and 0.5 (9.5 - 10.0).
Chip 1: one op 0.0 - 5.0 a step: busy 5.0, idle 5.0.
"""
import pytest

from benchmarks.harness import measure
from benchmarks.harness import trace_reduce as tr

MS = 1e-3


def _chip0(step_start):
    """Named by HLO text, as the TPU's trace names them."""
    t = step_start
    return [("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
             t + 0.0, t + 4.0),
            ("%all-gather.1 = bf16[8]{0} all-gather(bf16[2]{0} %p)",
             t + 3.0, t + 6.0),
            ("%flash_fwd = bf16[8]{0} custom-call(bf16[8]{0} %q), "
             'custom_call_target="tpu_custom_call"', t + 6.0, t + 8.0),
            ("%while.1 = (s32[]{:T(128)}, bf16[8]{0}) while((s32[], bf16[8]) "
             "%tuple), condition=%c, body=%b", t + 8.5, t + 9.5),
            ("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
             t + 8.5, t + 9.0),
            ("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop",
             t + 9.0, t + 9.5)]


def _s(events):
    return [(n, a * MS, b * MS) for n, a, b in events]


@pytest.fixture
def trace():
    chip0, mosaic = tr.labelled(_s(sum((_chip0(t) for t in (0, 10, 20)), [])))
    assert mosaic == {"flash_fwd_bf16_8"}
    ops = {0: chip0, 1: _s([("fusion", t, t + 5.0) for t in (1, 11, 21)])}
    programs = {0: _s([("step", t, t + 9.5) for t in (0, 10, 20)]
                      + [("tiny", 9.6, 9.7)]),
                1: _s([("step", t, t + 5.0) for t in (1, 11, 21)])}
    host = _s([("bench.step_call", 7.9, 8.6), ("bench.fetch_loss", 8.6, 17.5),
               ("bench.step_call", 17.9, 18.6)])
    return tr.build(ops, programs, host, "bench.step_call", skip=0)


def test_interval_arithmetic():
    assert tr.union([(3, 6), (0, 4), (8, 9), (9, 9)]) == [(0, 6), (8, 9)]
    assert tr.total([(0, 6), (8, 9)]) == 7
    assert tr.clip([(0, 6), (8, 9)], 5, 8.5) == [(5, 6), (8, 8.5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 1)], []) == [(0, 1)]


def test_control_flow_is_left_out_by_opcode_not_by_containment(trace):
    assert {name for name, _, _ in trace.ops[0]} == {
        "fusion_bf16_8", "all-gather_bf16_8", "flash_fwd_bf16_8"}
    assert len(trace.ops[0]) == 3 * 5
    # a kernel that spans a small operation of another unit stays
    events, _ = tr.labelled([
        ("%flash_fwd = bf16[8]{0} custom-call(bf16[8]{0} %q)", 0.0, 2.0),
        ("%copy-start.3 = (bf16[8]{0}, u32[]{:S(2)}) copy-start(bf16[8]{0} "
         "%p)", 1.0, 1.1)])
    assert [name for name, _, _ in events] == [
        "flash_fwd_bf16_8", "copy-start_bf16_8_u32"]
    assert tr.opcode("%a.1 = (f32[2]{0}, s32[]) while((f32[2], s32[]) %t), "
                     "body=%b") == "while"


def test_window_and_steps(trace):
    assert trace.steps == 2
    assert trace.window[0] == pytest.approx((0.0, 20 * MS))
    assert trace.window[1] == pytest.approx((1 * MS, 21 * MS))
    assert trace.window_s() == pytest.approx(20 * MS)


def test_busy_union_and_idle_share(trace):
    assert tr.total(trace.busy(0)) == pytest.approx(18.0 * MS)
    assert tr.total(trace.busy(1)) == pytest.approx(10.0 * MS)
    assert trace.busy_s() == pytest.approx(14.0 * MS)      # mean of chips
    idle_share = 1 - trace.busy_s() / trace.window_s()
    assert idle_share == pytest.approx(0.30)


def test_per_kernel_sums(trace):
    assert trace.per_step(0, r"flash_(fwd|bwd_dq|bwd_dkv)") == \
        pytest.approx(2.0 * MS)
    assert trace.per_step(0, r"all-gather|all-reduce") == \
        pytest.approx(3.0 * MS)
    sums = tr.sum_by_name(trace.ops[0], *trace.window[0])
    assert sums["fusion_bf16_8"] == pytest.approx(2 * 5.0 * MS)
    assert not any(name.startswith("while") for name in sums)


def test_exposed_collective_time(trace):
    exposed = measure._reader("layer_metrics", "exposed_collective_ms")
    collective = measure._reader("layer_metrics", "collective_ms_per_step")
    # the all-gather (3.0 - 6.0) runs alone after fusion.1 ends at 4.0
    assert exposed.reduce(trace, {}) == pytest.approx(2.0)
    assert collective.reduce(trace, {}) == pytest.approx(3.0)
    # an asynchronous all-reduce in flight 7.0 - 9.2 of each step joins
    # it: 5.2 in flight, of which 2.0 + 0.5 (the idle 8.0 - 8.5) have no
    # computation beside them
    trace.async_ops = {0: _s([("all-reduce-start", t + 7.0, t + 9.2)
                              for t in (0, 10, 20)])}
    flight = trace.in_flight(0, "^(all-gather|all-reduce)")
    assert tr.total(flight) / trace.steps == pytest.approx(5.2 * MS)
    assert exposed.reduce(trace, {}) == pytest.approx(2.5)
    assert collective.reduce(trace, {}) == pytest.approx(5.2)


def test_gap_attribution(trace):
    lo, hi = trace.window[0]
    gaps = tr.idle_gaps(trace.busy(0), lo, hi)
    assert [pytest.approx(g) for g in gaps] == [
        (8.0 * MS, 8.5 * MS), (9.5 * MS, 10 * MS),
        (18.0 * MS, 18.5 * MS), (19.5 * MS, 20 * MS)]
    by_host = tr.attribute_gaps(gaps, trace.host, trace.programs[0])
    assert by_host == pytest.approx({
        # 8.0 - 8.5 and 18.0 - 18.5: step_call spans, program executing
        "bench.step_call.inside_program": 1.0 * MS,
        # 9.5 - 10.0: under fetch_loss, between two executions
        "bench.fetch_loss.between_programs": 0.5 * MS,
        # 19.5 - 20.0: no span of the benchmark covers it
        "outside_spans.between_programs": 0.5 * MS})
    tiny = tr.attribute_gaps([(0.0, 5e-6), (1.0, 1.0 + 19e-6)], [], [])
    assert tiny == pytest.approx({"gaps_under_20us": 24e-6})


def test_op_label_joins_layers_and_keeps_kernel_names():
    assert tr.op_label("%fusion.7 = bf16[32,12,512,64]"
                       "{3,2,1,0:T(8,128)(2,1)} fusion(bf16[1]{0} %p), "
                       "kind=kOutput") == "fusion_bf16_32_12_512_64"
    assert tr.op_label("%convert_reduce_fusion.3 = "
                       "(f32[32,512]{1,0:T(8,128)}, bf16[32,512,768]"
                       "{2,1,0:T(8,128)(2,1)}) fusion(f32[4]{0} %a), "
                       "kind=kInput") == \
        "convert_reduce_fusion_f32_32_512_bf16_32_512_768"
    assert tr.op_label("all-reduce-start.12") == "all-reduce-start"
    assert tr.op_label("flash_fwd") == "flash_fwd"
