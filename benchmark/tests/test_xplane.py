"""The trace reduction against a trace recorded on four v5e chips
(`record_trace.py`; three steps of a two-layer GPT-2 under fsdp=4) and
against hand-made events whose answers are plain."""

import os

import pytest

from benchmark.families.gpt2 import Family
from benchmark.harness import xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu4.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce_file(RECORDED, spans=("dispatch", "sync"),
                              is_kernel=Family.is_attention_kernel)


def test_recorded_trace_reads_as_it_did(recorded):
    """Values as first reduced (PR 24): a change to the reduction that
    moves them has changed what every later PR's metrics mean."""
    assert recorded["devices"] == 4 and recorded["steps"] == 3
    assert recorded["window_s"] == pytest.approx(7.482908e-3, rel=1e-6)
    assert recorded["busy_s"] == pytest.approx(3.04240075e-3, rel=1e-6)
    assert recorded["collective_s"] == pytest.approx(2.22078675e-3, rel=1e-6)
    assert recorded["kernel_s"] == pytest.approx(7.886175e-5, rel=1e-6)
    assert recorded["device_ops"][0][0] == "all-reduce_bf16_8_256_1024_"
    assert recorded["device_ops"][0][1] == pytest.approx(8.730425e-4,
                                                         rel=1e-6)
    # XLA:TPU's fused reduce-scatter counts as the collective it is
    assert recorded["device_ops"][1][0] == \
        "fusion:kCustom:all-reduce-scatter_f32_512_8_128_"


def test_recorded_trace_is_consistent(recorded):
    # in this trace every collective is on the core's own line: nothing
    # computes beside it, so all of it is exposed
    assert recorded["collective_exposed_s"] == pytest.approx(
        recorded["collective_s"])
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    # forward and backward kernels of two layers, by their shapes
    assert sorted(recorded["kernels"]) == [
        "tpu_custom_call__bf16_2_256_256___bf16_2_256_256___bf16_2_256_25",
        "tpu_custom_call__bf16_2_256_256___f32_4_256_2__"]
    # the gaps, shared out among the loop's spans, are the idle time
    idle = recorded["window_s"] - recorded["busy_s"]
    assert sum(s for _, s in recorded["idle_gaps"]) == pytest.approx(idle)
    # at this toy size the host is the bottleneck: the device waits while
    # the loop dispatches
    assert recorded["idle_gaps"][0][0] == "dispatch"


def hand_made():
    """One device, times in ns.  Core: compute 0-100, an all-reduce
    100-150 (nothing beside it), compute 150-250 inside which a parent
    event nests a child, idle 250-300, compute 300-400.  In flight beside
    the core: an all-gather 80-120 (40 long, 20 of it under compute), and
    a copy-start that is no collective.  Host: dispatch 240-280, sync
    280-400."""
    core = [
        ("fusion:kLoop_f32_8_", 0, 100),
        ("all-reduce_f32_8_", 100, 150),
        ("while_f32_8_", 150, 250),
        ("tpu_custom_call_f32_8_", 170, 230),
        ("fusion:kLoop_f32_8_", 300, 400),
    ]
    flying = [("all-gather-start_f32_8_", 80, 120),
              ("copy-start_f32_8_", 0, 400)]
    modules = [("jit_step(1)", 0, 250), ("jit_step(1)", 300, 400)]
    host = [("dispatch", 240, 280), ("sync", 280, 400), ("other", 0, 400)]
    return [("/device:TPU:0", [("XLA Ops", core),
                               ("Async XLA Ops", flying),
                               ("XLA Modules", modules)]),
            ("/host:CPU", [("python", host)])]


def test_hand_made_events():
    r = xplane.reduce(hand_made(), spans=("dispatch", "sync"),
                      is_kernel=Family.is_attention_kernel)
    ns = 1e-9
    assert r["devices"] == 1 and r["steps"] == 2
    assert r["window_s"] == pytest.approx(400 * ns)
    assert r["busy_s"] == pytest.approx(350 * ns)
    # collectives: 80-150 merged = 70; exposed: 100-150 = 50
    assert r["collective_s"] == pytest.approx(70 * ns)
    assert r["collective_exposed_s"] == pytest.approx(50 * ns)
    # the nested kernel takes 60 of its parent's 100
    assert r["kernel_s"] == pytest.approx(60 * ns)
    ops = dict(r["device_ops"])
    assert ops["while_f32_8_"] == pytest.approx(40 * ns)
    assert ops["fusion:kLoop_f32_8_"] == pytest.approx(200 * ns)
    # the gap 250-300: 30 under dispatch, 20 under sync
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"dispatch": 30 * ns, "sync": 20 * ns})


def test_gap_no_span_covers_is_named_so():
    planes = hand_made()
    planes[1] = ("/host:CPU", [("python", [("dispatch", 240, 260)])])
    r = xplane.reduce(planes, spans=("dispatch", "sync"))
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"dispatch": 10e-9, xplane.NO_SPAN: 40e-9})


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce([("/host:CPU", [("python", [("sync", 0, 5)])])])


@pytest.mark.parametrize("text, name", [
    ("%fusion.277 = s32[1,2,2,128]{3,2,1,0:T(2,128)S(1)} fusion(s32[2,256,3]"
     "{1,0,2} %x), kind=kLoop, calls=%fused_computation.385",
     "fusion:kLoop_s32_1_2_2_128_"),
    ("%all-reduce.5 = bf16[16,1024,6400]{2,1,0} all-reduce(bf16[16,1024,"
     "6400]{2,1,0} %y), channel_id=3", "all-reduce_bf16_16_1024_6400_"),
    ('%branch_0_fun.4 = (bf16[2,256,256]{2,1,0}, f32[4,256,2]{2,1,0}) '
     'custom-call(bf16[2,256,256]{2,1,0} %q), custom_call_target='
     '"tpu_custom_call"', "tpu_custom_call__bf16_2_256_256___f32_4_256_2__"),
    ("%all-gather-start.1 = (f32[4]{0}, f32[16]{0}) all-gather-start("
     "f32[4]{0} %p)", "all-gather-start__f32_4___f32_16__"),
    ("%fusion.52 = f32[201552,8,128]{2,1,0} fusion(f32[16,1024,50304]{2,1,0}"
     " %l), kind=kCustom, calls=%all-reduce-scatter.48",
     "fusion:kCustom:all-reduce-scatter_f32_201552_8_128_"),
    ("barrier-cores.12", "barrier-cores"),
])
def test_op_names(text, name):
    assert xplane.op_name(text) == name
    assert xplane.is_collective(name) == ("all-" in name)
