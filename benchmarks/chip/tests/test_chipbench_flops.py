"""FLOP and byte counts against hand counts and the program's own count."""
import dataclasses
import json

from chipbench import flops, manifest, reference

QWEN = json.loads((manifest.HERE / "configs" / "qwen3-4b.json").read_text())


def test_param_count_matches_the_program():
    from repro.configs import get_config
    from repro.models import build_model
    cut = dataclasses.replace(get_config("qwen3-4b"), n_layers=3,
                              vocab=18944)
    a = reference.arch(QWEN)
    assert reference.param_count(a) == 351_291_648
    assert build_model(cut).param_count() == 351_291_648
    # ArchConfig's own count leaves out the q/k norm scales (2 x 128 a layer)
    assert cut.param_count() + 3 * 2 * 128 == 351_291_648


def test_matmul_params_by_hand():
    a = reference.arch(QWEN)
    # per layer: q 2560x4096, k and v 2560x1024 each, o 4096x2560, MLP
    # three 2560x9728; plus the tied head 2560x18944
    per_layer = 2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728
    assert flops.matmul_params(a) == 3 * per_layer + 2560 * 18944
    # norms are the only parameters left out
    norms = 3 * (2 * 2560 + 2 * 128) + 2560
    assert flops.matmul_params(a) == reference.param_count(a) - norms


def test_train_flops_per_token_by_hand():
    a = reference.arch(QWEN)
    seq = 2048
    attn = 3 * 4 * 32 * 128 * (seq + 1) / 2 * 3      # fwd+bwd, 3 layers
    want = 6 * flops.matmul_params(a) + attn
    assert flops.train_flops_per_token(a, seq) == want
    # 4,096 tokens a step: 8.6 TFLOP of matmuls, 0.6 TFLOP of attention
    assert abs(4096 * want / 1e12 - 9.25) < 0.01


def test_round_kernel_bytes_by_hand():
    # bf16 theta + f32 lam + f32 previous mean + two bf16 neighbour rows
    # read (14 B), bf16 theta + f32 lam + f32 mean written (10 B)
    assert flops.round_kernel_bytes(1000, 2, 2, 2000) == 24_000
    assert flops.round_kernel_bytes(1000, 4, 2, 2000) == 28_000
    # an int8 wire: one byte an element and a 4-byte scale per leaf (3)
    assert flops.round_kernel_bytes(1000, 2, 2, 1012) == 20_000 + 2024
