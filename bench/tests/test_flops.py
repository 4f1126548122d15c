"""FLOP and byte functions against hand counts at small shapes."""

import numpy as np
import pytest

from bench import flops
from repro.kernels.paged_attention import decode_kv_bytes

MODEL = {"n_layer": 2, "n_embd": 8, "n_head": 2, "n_inner": None,
         "vocab_size": 10}


def test_dense_params_by_hand():
    # per layer: q, k, v, o (4 * 8 * 8) + MLP (2 * 8 * 32)
    assert flops.dense_params(MODEL) == 2 * (256 + 512)


def test_keys_seen_by_hand():
    assert flops.keys_seen(0, 1) == 1
    assert flops.keys_seen(5, 1) == 6              # decode at position 5
    assert flops.keys_seen(0, 3) == 1 + 2 + 3
    assert flops.keys_seen(4, 2) == 5 + 6


def test_model_flops_by_hand():
    # one decode row at position 3 (4 keys) that emits a token:
    # 2 * 1536 weights + 4 * L * d * keys + 2 * d * V
    got = flops.model_flops(MODEL, [(3, 1)], logits_rows=1)
    assert got == 2 * 1536 + 4 * 2 * 8 * 4 + 2 * 8 * 10


def test_attn_cost_by_hand():
    # H=2, hd=4; one row, 2 queries at positions 1, 2 (keys 2 and 3)
    f, b = flops.attn_cost(MODEL, [(1, 2)], kv_itemsize=4, selected=3)
    assert f == 4 * 4 * 2 * 5 + 2 * 4 * 3
    assert b == 2 * 3 * 2 * 4 * 4 + 2 * 2 * 2 * 4 * 4


@pytest.mark.parametrize("lengths", [[1], [16], [17, 40, 100], [64, 1, 33]])
def test_attn_bytes_within_what_the_kernel_reads(lengths):
    """Decode rows: the least bytes never exceed the live blocks the fused
    kernel DMAs (decode_kv_bytes), less the q and output the kernel also
    moves."""
    model = {"n_layer": 1, "n_embd": 64, "n_head": 4, "vocab_size": 10}
    rows = [(n - 1, 1) for n in lengths]
    _, b = flops.attn_cost(model, rows, kv_itemsize=4)
    qo = 2 * len(rows) * 64 * 4
    _, fused = decode_kv_bytes(np.array(lengths), n_max=8, block_size=16,
                               bytes_per_token=64 * 4)
    assert b - qo <= fused


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 10.0, peak) == 10.0
    assert flops.least_time(100.0, 50.0, peak) == 5.0
