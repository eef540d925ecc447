"""Work counts against the paper's Table I, and the peak table."""
import json
import os

import pytest

from bench import spec, workcount
from bench.reference import module


def config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def elementwise_ops(layers, cfg):
    """Ops Table I may count beside 2 x MAC: one bias add and one ReLU per
    conv/dense output element, and the 2x2 max-pool comparisons."""
    ops = 0
    for layer in layers:
        out = layer.h_out * layer.w_out * layer.cout
        ops += 2 * out
        if layer.kind == "conv" and cfg.get("pool"):
            ops += out // cfg["pool"] ** 2 * (cfg["pool"] ** 2 - 1)
    return ops


def valid_macs(layer):
    """MACs of one sample whose input tap lies inside the image (the
    SAME-padding taps left out)."""
    def taps(n_in, n_out):
        pad = max((n_out - 1) * layer.stride + layer.k - n_in, 0) // 2
        return sum(1 for o in range(n_out) for t in range(layer.k)
                   if 0 <= o * layer.stride + t - pad < n_in)
    return (taps(layer.h, layer.h_out) * taps(layer.w, layer.w_out)
            * layer.cout * layer.cin)


def test_cnet_model_ops_match_table_one_within_the_elementwise_share():
    cfg = config("cnet_accel")
    layers = module(cfg).layers(cfg)
    ours = workcount.model_ops(layers)
    assert ours == 2 * 455_999_672
    # Table I counts 6.24 M ops more than 2 x MAC (0.68%): less than the
    # 10.4 M that bias adds, ReLUs and pools account for
    gap = cfg["paper_ops"] - ours
    assert 0 < gap <= elementwise_ops(layers, cfg)


def test_vae_model_ops_overshoot_table_one_by_its_assumed_widths():
    cfg = config("vae_accel")
    layers = module(cfg).layers(cfg)
    ours = workcount.model_ops(layers)
    assert ours == 85_192_704
    # The channel widths (8, 32, 96, 144, 144) are assumed: fit to Table
    # I's parameter count, with no published layer list to take them
    # from (the configuration's ``assumed``). They fail the rule the
    # CNet meets: 2 x MAC alone is 2.1% over Table I's total ops, and
    # element-wise ops only add to a count. Nor do the SAME-padding taps
    # explain it (leaving them out undershoots by 3.2%). So these are not
    # the published widths; the test holds the gap where it stands so
    # that the day the widths come from a publication, it shows.
    assert ours / cfg["paper_ops"] - 1 == pytest.approx(0.02128, abs=2e-4)
    valid = 2 * sum(valid_macs(layer) for layer in layers)
    assert valid / cfg["paper_ops"] - 1 == pytest.approx(-0.03227, abs=2e-4)


@pytest.mark.parametrize("name", ["cnet_accel", "vae_accel"])
def test_parameter_counts_match_table_one_within_half_a_percent(name):
    cfg = config(name)
    layers = module(cfg).layers(cfg)
    params = sum(layer.k * layer.k * layer.cin * layer.cout + layer.cout
                 for layer in layers)
    assert abs(params / cfg["paper_params"] - 1) < 0.005


def test_cnet_fc1_call_counts():
    cfg = config("cnet_accel")
    fc1 = {l.name: l for l in module(cfg).layers(cfg)}["fc1"]
    assert (fc1.cin, fc1.cout) == (32769, 92)
    assert workcount.ops(fc1, 32) == 2 * 32 * 32769 * 92
    # int8 input and output per sample, int8 weights, f32 scale and bias
    assert workcount.bytes_moved(fc1, 32) == (
        32 * (32769 + 92) + 32769 * 92 + 8 * 92)


def test_least_time_is_the_larger_of_compute_and_bandwidth():
    peak = workcount.peaks("TPU v5 lite")
    cfg = config("cnet_accel")
    conv0 = module(cfg).layers(cfg)[0]
    t = workcount.least_seconds(conv0, 32, peak)
    assert t == max(workcount.ops(conv0, 32) / 393e12,
                    workcount.bytes_moved(conv0, 32) / 819e9)
    # conv0 writes 48 channels at full resolution: bandwidth bounds it
    assert t == workcount.bytes_moved(conv0, 32) / 819e9


def test_published_v5e_peaks():
    peak = workcount.peaks("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "source", ""])
def test_unknown_device_kind_fails(kind):
    with pytest.raises(KeyError):
        workcount.peaks(kind)
