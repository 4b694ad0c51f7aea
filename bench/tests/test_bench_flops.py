"""The peaks table and the operation and byte counts, against counts made
by hand on a one-layer GPT-2 of width 2 (d_ff 4, vocabulary 3, rank 1)."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import pytest

import flops
from common import peaks

CFG = {"n_layer": 1, "n_embd": 2, "n_inner": 4, "vocab_size": 3,
       "lora_rank": 1}


def test_peaks_keyed_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_train_flops_by_hand():
    # one token, context 1.  Forward: q, k, v, o 4 x 2*2*2 = 32; MLP
    # 2 x 2*2*4 = 32; q k^T and p v 2 x 2*2*1 = 8; LoRA on q and v
    # 2 x (4 + 4) = 16; head 2*2*3 = 12.  Sum 100.
    # Backward: head dX 12; MLP dX 32; o dX 8; dP, dV, dQ 3 x 4 = 12 (layer
    # 0 needs no dK and no dX through q, k, v); LoRA z2, dA, dB 2 x 12 = 24
    # (no low-rank dX in layer 0).  Sum 88.
    assert flops.train_flops_per_token(CFG, 1) == 188.0


def test_kernel_counts_by_hand():
    # x (2, 3) w (3, 4) a (1, 3) b (4, 1), f32
    assert flops.lora_fwd(2, 3, 4, 1) == (76.0, 132.0)
    assert flops.lora_dx(2, 4, 3, 1) == flops.lora_fwd(2, 3, 4, 1)
    assert flops.rank_reduce(2, 1, 3) == (12.0, 44.0)


def test_roofline_takes_the_larger_bound():
    p = peaks("TPU v5 lite")
    assert flops.roofline_s(197e12, 1.0, p) == pytest.approx(1.0)
    assert flops.roofline_s(1.0, 819e9, p) == pytest.approx(1.0)
