"""Assignment fidelity: every arch config carries the exact assigned numbers."""

import pytest

from repro.configs import ARCHS, SHAPES, all_cells, applicable_shapes

# (arch, d_model, n_layers, n_heads, n_kv, d_ff, vocab)
ASSIGNED = {
    "jamba-v0.1-52b": (4096, 32, 32, 8, 14336, 65536),
    "codeqwen1.5-7b": (4096, 32, 32, 32, 13440, 92416),
    "gemma2-2b": (2304, 26, 8, 4, 9216, 256000),
    "nemotron-4-15b": (6144, 32, 48, 8, 24576, 256000),
    "stablelm-3b": (2560, 32, 32, 32, 6912, 50304),
    "rwkv6-7b": (4096, 32, 0, 0, 14336, 65536),
    "seamless-m4t-medium": (1024, 12, 16, 16, 4096, 256206),
    "llama4-maverick-400b-a17b": (5120, 48, 40, 8, 8192, 202048),
    "olmoe-1b-7b": (2048, 16, 16, 16, 1024, 50304),
    "internvl2-2b": (2048, 24, 16, 8, 8192, 92553),
    "moonlight-16b-a3b": (2048, 27, 16, 16, 1408, 163840),
}

MOE = {
    "jamba-v0.1-52b": (16, 2),
    "llama4-maverick-400b-a17b": (128, 1),
    "olmoe-1b-7b": (64, 8),
    "moonlight-16b-a3b": (64, 6),
}


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_assigned_numbers_verbatim(arch):
    cfg = ARCHS[arch]
    d, L, H, Kv, F, V = ASSIGNED[arch]
    assert cfg.d_model == d
    assert cfg.n_layers == L
    assert cfg.n_heads == H
    assert cfg.n_kv_heads == Kv
    assert cfg.vocab_size == V
    ff = cfg.moe.d_ff if (cfg.moe is not None and arch != "jamba-v0.1-52b") else cfg.d_ff
    assert ff == F, (arch, ff, F)


def test_moe_specs():
    for arch, (e, k) in MOE.items():
        cfg = ARCHS[arch]
        assert cfg.moe.n_experts == e and cfg.moe.top_k == k, arch


def test_family_signatures():
    assert any(s.mixer == "mamba" for s in ARCHS["jamba-v0.1-52b"].pattern)
    # Jamba 1:7 attention:mamba
    mixers = [s.mixer for s in ARCHS["jamba-v0.1-52b"].pattern]
    assert mixers.count("attn") == 1 and mixers.count("mamba") == 7
    assert all(s.mixer == "rwkv" for s in ARCHS["rwkv6-7b"].pattern)
    assert ARCHS["gemma2-2b"].pattern[0].mixer == "attn_local"  # local/global alternation
    assert ARCHS["gemma2-2b"].attn_softcap == 50.0 and ARCHS["gemma2-2b"].final_softcap == 30.0
    assert ARCHS["nemotron-4-15b"].activation == "relu2"
    assert ARCHS["stablelm-3b"].rope_fraction == 0.25
    assert ARCHS["seamless-m4t-medium"].is_encdec and ARCHS["seamless-m4t-medium"].n_enc_layers == 12
    assert ARCHS["llama4-maverick-400b-a17b"].moe.n_shared == 1
    assert ARCHS["olmoe-1b-7b"].qk_norm
    assert ARCHS["internvl2-2b"].frontend == "vision"
    assert ARCHS["seamless-m4t-medium"].frontend == "audio"


def test_shape_cells():
    assert SHAPES["train_4k"].seq_len == 4096 and SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768 and SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].seq_len == 32768 and SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288 and SHAPES["long_500k"].global_batch == 1
    assert SHAPES["decode_32k"].kind == "decode" and SHAPES["long_500k"].kind == "decode"


def test_cell_count_and_skips():
    """11 archs × 4 shapes = 44 cells; long_500k runs only for the
    sub-quadratic archs (jamba, gemma2, rwkv6)."""
    runnable = all_cells()
    assert len(runnable) == 36  # 44 − 8 long_500k skips
    long_runners = {c.name for c, s in runnable if s.name == "long_500k"}
    assert long_runners == {"jamba-v0.1-52b", "gemma2-2b", "rwkv6-7b"}
    for cfg in ARCHS.values():
        shapes = {s.name for s in applicable_shapes(cfg)}
        assert {"train_4k", "prefill_32k", "decode_32k"} <= shapes
