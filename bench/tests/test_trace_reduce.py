"""The reduction from a plan's HLO and a profile to the device numbers."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

HLO = """HloModule jit_run_queue_pallas_batch, is_scheduled=true, entry_computation_layout={(f32[32]{0})->f32[64,10]{1,0}}

%fused_computation.55 (param_0: f32[64,32,32,3], param_1: f32[3,3,3,32]) -> f32[64,32,32,32] {
  %param_0 = f32[64,32,32,3]{3,2,1,0} parameter(0)
  %param_1 = f32[3,3,3,32]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = f32[64,32,32,32]{3,2,1,0} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

%fused_computation.7 (param_0: f32[64,10]) -> f32[64,10] {
  %param_0 = f32[64,10]{1,0} parameter(0)
  ROOT %add.3 = f32[64,10]{1,0} add(%param_0, %param_0)
}

ENTRY %main.1 (Arg_0.1: f32[32]) -> f32[64,10] {
  %fusion.55 = f32[64,32,32,32]{3,2,1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.55
  %fused_spike_accum_pallas.6 = f32[256,32,32,32]{3,2,1,0} custom-call(%c, %d), custom_call_target="tpu_custom_call", backend_config={}
  %custom-call = f32[64,32,32,3]{3,2,1,0} custom-call(%e), custom_call_target="ConcatBitcast"
  ROOT %fusion.7 = f32[64,10]{1,0} fusion(%f), kind=kLoop, calls=%fused_computation.7
}
"""


def test_stage_ops_are_convolutions_and_mosaic_kernels():
    module, ops = trace_reduce.stage_ops(HLO)
    assert module == "jit_run_queue_pallas_batch"
    assert ops == {"fusion.55", "fused_spike_accum_pallas.6"}


def test_union_and_inside():
    merged = trace_reduce._union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert merged == [[0, 2.5], [3, 4]]
    spans = [(0.0, 1.0), (2.0, 3.0)]
    assert trace_reduce._inside(0.5, spans)
    assert not trace_reduce._inside(1.5, spans)
    assert trace_reduce._inside(3.0, spans)
    assert not trace_reduce._inside(-1.0, spans)


def test_find_xplane_needs_a_profile(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))


# A profile of two steps of cifar10-event-saturated on one TPU v5e chip,
# recorded by ``bench/run.py --seconds 1 --trace 1``, and the conv-stage
# ops of its plan (``stage_ops`` of the plan's HLO for that chip).
RECORDED = os.path.join(BENCH, "testdata", "event-saturated-2steps.xplane.pb")
EVENT_PLAN = ("jit_run_queue_pallas_batch", {
    "add_compare_fusion.1", "fusion.55",
    *(f"fused_spike_accum_pallas.{i}" for i in range(6, 12))})


def test_recorded_trace_reduces_to_its_device_numbers():
    import work
    from window import Batch

    net = work.parse("32C3-32C3-P3-64C3-64C3-P3-128C3-128C3-128C3-10", 32, 3)
    host, ops, modules = trace_reduce.load(RECORDED)
    steps = [s for s in host if s[0] == "bench.step"]
    assert len(steps) == 2 and list(ops) == ["/device:TPU:0"]
    batches = [Batch(a, b, 64, 64, 0.0, 0.0, 0.0) for _, a, b in steps]
    s = trace_reduce.reduce(RECORDED, {"T": 4}, net, batches, {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, [EVENT_PLAN])
    assert s.window_s == pytest.approx(1.474590276)
    assert s.busy_s == pytest.approx(1.414495499)
    # the six Pallas kernels and the analog conv take nearly all the time
    assert s.conv_op_s == pytest.approx(1.412059437)
    assert s.conv_op_s <= s.busy_s <= s.window_s
    least = sum(work.stage_least_s(st, 4, 64, 197e12, 819e9)[0]
                for st in net.stages) * 2
    assert s.conv_least_s == pytest.approx(least)
    assert s.conv_bound == "bytes"
    top, seconds = s.breakdown["device_ops"][0]
    assert top == "fused_spike_accum_pallas.6"
    assert seconds == pytest.approx(1.081168558)
    assert len(s.breakdown["device_ops"]) == 10
    (host_span, idle), = s.breakdown["idle_gaps"]
    assert host_span == "bench.step"
    assert idle == pytest.approx(s.window_s - s.busy_s)
