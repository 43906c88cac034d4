"""Model FLOPs against a hand count, and the peaks table."""

import pytest

from chipbench import flops, harness


def _config(name):
    return harness.config_of(harness.load_spec(), name)


def test_stablelm_step_flops_by_hand():
    # per token, per layer: q,k,v,o 4·2·2560·2560; attention 2·2·2560·(2049/2);
    # SwiGLU 3·2·2560·6912; then the head 2·2560·50304.  ×3 for backward, ×8192 tokens.
    layer = 4 * 2 * 2560 * 2560 + 2 * 2 * 2560 * 2049 / 2 + 3 * 2 * 2560 * 6912
    per_token = 4 * layer + 2 * 2560 * 50304
    assert flops.train_step(_config("stablelm-3b"), 4, 2048) == pytest.approx(3 * per_token * 8192)
    assert flops.train_step(_config("stablelm-3b"), 4, 2048) == pytest.approx(2.30e13, rel=0.01)


def test_olmoe_counts_only_the_routed_experts():
    # router 2·2048·64 and 8 of the 64 experts, each 3·2·2048·1024; no dispatch, no capacity
    layer = 4 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 2049 / 2 + 2 * 2048 * 64 + 8 * 3 * 2 * 2048 * 1024
    per_token = layer + 2 * 2048 * 50304
    assert flops.train_step(_config("olmoe-1b-7b"), 4, 2048) == pytest.approx(3 * per_token * 8192)
    assert flops.train_step(_config("olmoe-1b-7b"), 4, 2048) == pytest.approx(8.58e12, rel=0.01)


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_of("TPU v9 imaginary")
