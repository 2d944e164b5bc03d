"""The yardstick's arithmetic against values worked by hand: operations per
token, the attention kernels' operations and bytes, parameter counts, the
roofline's least time, for both configurations."""

import pytest

from benchmark.harness import registry

MEDIUM = dict(L=24, H=16, E=1024)
XL = dict(L=48, H=25, E=1600)
ROWS, POSITIONS, BATCH, SEQ, D = 50304, 1024, 16, 1024, 64


def family(name):
    return registry.family(registry.config(name))


@pytest.mark.parametrize("name, shape, by_hand", [
    # 6 * (12 L E^2 + rows E) + 12 L E S
    ("gpt2-medium", MEDIUM, 2_422_996_992),
    ("gpt2-xl-fsdp4", XL, 10_273_996_800),
])
def test_flops_per_token(name, shape, by_hand):
    L, E = shape["L"], shape["E"]
    assert 6 * (12 * L * E * E + ROWS * E) + 12 * L * E * SEQ == by_hand
    assert family(name).flops_per_token(SEQ) == by_hand


@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-xl-fsdp4"])
def test_flops_are_the_programs_own_count(name):
    """The copy in the benchmark and `gpt2.count_flops_per_token` agree
    today; the copy is what later PRs cannot change."""
    from ray_tpu.models import gpt2

    fam = family(name)
    original = gpt2.count_flops_per_token(
        gpt2.GPT2Config(n_layer=fam.n_layer, n_head=fam.n_head,
                        n_embd=fam.n_embd), SEQ)
    assert fam.flops_per_token(SEQ) == original


@pytest.mark.parametrize("name, shape, params", [
    # rows E + positions E + L (12 E^2 + 13 E) + 2 E
    ("gpt2-medium", MEDIUM, 354_871_296),
    ("gpt2-xl-fsdp4", XL, 1_557_686_400),
])
def test_param_count(name, shape, params):
    assert family(name).param_count() == params


@pytest.mark.parametrize("name, shape, flops, nbytes", [
    # per layer: 6 products of 2 B H S^2 D, halved for causality;
    # 12 tensors of B S H D in bf16 and two of B H S in f32
    ("gpt2-medium", MEDIUM, 2_473_901_162_496, 9_714_008_064),
    ("gpt2-xl-fsdp4", XL, 7_730_941_132_800, 30_356_275_200),
])
def test_attention_cost(name, shape, flops, nbytes):
    L, H = shape["L"], shape["H"]
    assert L * 6 * (2 * BATCH * H * SEQ * SEQ * D) // 2 == flops
    assert L * (12 * BATCH * SEQ * H * D * 2 + 2 * BATCH * H * SEQ * 4) \
        == nbytes
    cost = family(name).attention_cost(BATCH, SEQ)
    assert cost == {"flops": flops, "bytes": nbytes}


def test_attention_roofline_is_compute_bound_on_v5e():
    """medium on one chip: 2.474e12 / 197e12 = 12.56 ms of arithmetic
    against 9.714e9 / 819e9 = 11.86 ms of traffic, per step."""
    metric = registry.metric("attn_roofline_share")
    config = registry.config("gpt2-medium")
    obs = {"family": registry.family(config), "chips": 1,
           "traffic": registry.traffic("resident"),
           "peaks": registry.peaks("TPU v5 lite"),
           "trace": {"kernel_s": 0.2, "steps": 4}}
    seconds, bound = metric.least_seconds(obs)
    assert bound == "compute"
    assert seconds == pytest.approx(0.0125579, rel=1e-4)
    # 4 steps' least time over 0.2 s of kernels
    assert metric.read(obs) == pytest.approx(100 * 4 * 0.0125579 / 0.2, rel=1e-4)


def test_unknown_device_has_no_peaks():
    with pytest.raises(SystemExit):
        registry.peaks("TPU v9 imaginary")
