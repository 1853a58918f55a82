"""The reduction from trace to numbers, on a constructed trace."""
import math

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import kernels, trace

MS = 1_000_000


def _trace():
    ops = {
        0: [("fusion.1", 0, 4 * MS), ("jvp__.1", 3 * MS, 6 * MS),
            ("collective-permute-start.2", 6 * MS, 8 * MS),
            ("fusion.2", 7 * MS, 9 * MS), ("all-reduce.3", 11 * MS, 12 * MS)],
        1: [("fusion.1", 0, 10 * MS)],
    }
    spans = [("bench/step", 0, 6 * MS), ("bench/fetch", 0, 1 * MS),
             ("bench/block", 1 * MS, 6 * MS), ("bench/step", 6 * MS, 12 * MS),
             ("bench/place", 8.5 * MS, 10.2 * MS),
             ("bench/dispatch", 10.2 * MS, 11 * MS)]
    return trace.from_events(ops, spans)


def test_window_and_steps():
    tr = _trace()
    assert tr.window == (0, 12 * MS)
    assert tr.steps() == 2
    assert tr.window_s == pytest.approx(0.012)


def test_union_of_busy_intervals():
    tr = _trace()
    assert trace.busy_ns(tr.ops[0], tr.window) == 10 * MS
    assert trace.busy_ns(tr.ops[1], tr.window) == 10 * MS
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_idle_gaps_and_their_host_span():
    tr = _trace()
    assert trace.idle_gaps(tr.ops[0], tr.window) == [(9 * MS, 11 * MS)]
    assert trace.span_over(tr.spans, 9 * MS, 11 * MS) == "place"
    assert trace.span_over(tr.spans, 10.5 * MS, 11 * MS) == "dispatch"
    assert trace.span_over(tr.spans, 12 * MS, 13 * MS) == "none"
    bd = trace.breakdown(tr)
    assert bd["idle_gaps"] == [["place", pytest.approx(0.002)]]
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.007)]


HLO = (
    '  %jvp__.1 = (bf16[4,21,256,512]{3,2,1,0}, bf16[4,21,256,512]{3,2,1,0},'
    ' f32[4,21,256,2048]{3,2,1,0}, f32[4,21,256,512]{3,2,1,0},'
    ' f32[4,21,256,2048]{3,2,1,0}, f32[4,21,256,512]{3,2,1,0})'
    ' custom-call(%a, %a, %b, %c, %d, %e, %f, %g),'
    ' custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{bf16[4,21,256,1024]{3,2,1,0}, bf16[4,21,256,1024]{3,2,1,0},'
    ' bf16[4,1024,2048]{2,1,0}, bf16[4,512,2048]{2,1,0},'
    ' f32[4,1,2048]{2,1,0}, bf16[4,1024,2048]{2,1,0},'
    ' bf16[4,512,2048]{2,1,0}, f32[4,1,2048]{2,1,0}},'
    ' frontend_attributes={kernel_metadata={}}\n'
    '  %transpose_jvp___.2 = (bf16[4,21,256,1024]{3,2,1,0},'
    ' f32[4,1024,2048]{2,1,0}, f32[4,512,2048]{2,1,0}, f32[4,1,2048]{2,1,0})'
    ' custom-call(%p, %q), custom_call_target="tpu_custom_call",'
    ' operand_layout_constraints={bf16[4,21,256,512]{3,2,1,0},'
    ' f32[4,21,256,2048]{3,2,1,0}, f32[4,21,256,512]{3,2,1,0},'
    ' bf16[4,21,256,1024]{3,2,1,0}, bf16[4,1024,2048]{2,1,0},'
    ' bf16[4,512,2048]{2,1,0}}, metadata={}\n'
    '  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop\n')


def test_mosaic_classification_and_lstm_cost():
    calls = kernels.mosaic_calls(HLO)
    assert sorted(calls) == ["jvp__.1", "transpose_jvp___.2"]
    rows = 4 * 21 * 256
    fwd = kernels.lstm_cost(calls["jvp__.1"])
    # two directions, each x Wx (1024 x 2048) and h Wh (512 x 2048)
    assert fwd[0] == 2 * 2 * rows * 2048 * (1024 + 512)
    bwd = kernels.lstm_cost(calls["transpose_jvp___.2"])
    # one direction, four matmuls: dx, dh, dWx, dWh
    assert bwd[0] == 4 * rows * 2048 * (1024 + 512)
    assert bwd[1] == kernels.nbytes(calls["transpose_jvp___.2"]["results"]
                                    + calls["transpose_jvp___.2"]["operands"])
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = kernels.min_time_s(*fwd, peak)
    assert bound == "compute" and math.isclose(t, fwd[0] / 197e12)


def test_a_reader_never_reads_silence_as_absence():
    tr = _trace()
    assert trace.matched(tr, {"jvp__.1", "jvp__.5"}, "LSTM kernels")
    assert not trace.matched(tr, set(), "LSTM kernels")
    # the compiled step has such ops but the trace names none of them
    with pytest.raises(LookupError):
        trace.matched(tr, {"transpose_jvp___.2"}, "LSTM kernels")


@pytest.mark.parametrize("event, name", [
    # as a v5e trace's "XLA Ops" line names them: the instruction's text
    ("%vmap_jvp___.10 = (bf16[4,21,256,512]{3,2,1,0:T(8,128)(2,1)}, "
     "f32[4,21,256,2048]{3,2,1,0:T(8,128)}) custom-call(bf16[4,21,256,1024]"
     "{3,2,1,0:T(8,128)(2,1)} %copy-done.35), "
     'custom_call_target="tpu_custom_call"', "vmap_jvp___.10"),
    ("%collective-permute-done.3 = bf16[4,1024,2048]{2,1,0} "
     "collective-permute-done(%collective-permute-start.3)",
     "collective-permute-done.3"),
    ("fusion.7", "fusion.7"),
])
def test_op_name_is_the_instruction_name(event, name):
    assert trace.op_name(event) == name


def test_window_span_sets_the_window():
    ops = {0: [("fusion.1", 2 * MS, 9 * MS)]}
    spans = [("bench/window", 1 * MS, 10 * MS), ("bench/step", 1 * MS, 2 * MS),
             ("bench/step", 2 * MS, 3 * MS), ("bench/drain", 3 * MS, 10 * MS)]
    tr = trace.from_events(ops, spans)
    assert tr.window == (1 * MS, 10 * MS) and tr.steps() == 2
    # an idle stretch is named by the span open in it, never the window's
    # or a step's
    gaps = trace.breakdown(tr)["idle_gaps"]
    assert [g[0] for g in gaps] == ["none", "drain"]
    assert [g[1] for g in gaps] == pytest.approx([0.001, 0.001])
