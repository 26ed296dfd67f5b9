"""Operation and byte counts against numbers worked by hand."""

import pytest

from benchmark import manifest, opcount

MISTRAL = manifest.load_json(manifest.HERE + "/configs/mistral-7b-l16-serve.json")
QWEN = manifest.load_json(manifest.HERE + "/configs/qwen2-7b-l12-serve.json")


def test_layer_and_head_parameters():
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    assert opcount.layer_matmul_params(MISTRAL) == (
        16777216 + 2 * 4194304 + 16777216 + 3 * 58720256) == 218103808
    assert opcount.head_params(MISTRAL) == 131072000
    # q and o 3584x3584, k and v 3584x512, three 3584x18944
    assert opcount.layer_matmul_params(QWEN) == (
        2 * 12845056 + 2 * 1835008 + 3 * 67895296) == 233046016
    assert opcount.kv_bytes_per_token(MISTRAL) == 2 * 8 * 128 * 2 * 16 == 65536
    assert opcount.kv_bytes_per_token(QWEN) == 2 * 4 * 128 * 2 * 12 == 24576


def test_weight_bytes():
    want = 2 * (16 * 218103808 + 2 * 131072000 + 2 * 4096 * 16 + 4096)
    assert opcount.weight_bytes(MISTRAL) == want == 7503880192
    bias = 12 * 128 * (28 + 8)
    want = 2 * (12 * 233046016 + 2 * 3584 * 152064 + 2 * 3584 * 12 + 3584
                + bias)
    assert opcount.weight_bytes(QWEN) == want


def test_keys_seen_with_and_without_a_window():
    assert opcount.keys_seen(0, 4) == 1 + 2 + 3 + 4
    assert opcount.keys_seen(10, 2) == 11 + 12
    assert opcount.keys_seen(0, 6, window=4) == 1 + 2 + 3 + 4 + 4 + 4
    assert opcount.keys_seen(5000, 1, window=4096) == 4096
    assert opcount.window_of(MISTRAL) == 4096 and opcount.window_of(QWEN) == 0


def test_decode_step_needs_one_decoding_row():
    # one row, 99 cached, 1 fed, sampled: 100 keys seen, 100 read + 1 written
    n = opcount.decode_step_needs(MISTRAL, [(99, 1, 1)])
    layers = 2 * 16 * 218103808 * 1
    attn = 16 * 4 * 32 * 128 * 100
    head = 2 * 131072000
    assert n["flops"] == layers + attn + head == 7267680256
    want_bytes = (2 * 16 * 218103808 + 2 * 131072000 + 65536 * 101
                  + 2 * 4096 + 4 * 32000)
    assert n["bytes"] == want_bytes == 7248221184
    peaks = opcount.peaks_for("TPU v5 lite")
    least = opcount.least_seconds(n, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(7248221184 / 819e9)


def test_decode_step_needs_prefill_rows_do_not_read_the_head():
    n = opcount.decode_step_needs(MISTRAL, [(0, 32, 0), (64, 32, 0)])
    keys = sum(range(1, 33)) + sum(range(65, 97))
    assert n["sampled"] == 0 and n["fed"] == 64
    assert n["flops"] == 2 * 16 * 218103808 * 64 + 16 * 4 * 32 * 128 * keys
    assert n["bytes"] == (2 * 16 * 218103808 + 65536 * (32 + 96 + 64)
                          + 2 * 4096 * 64)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        opcount.peaks_for("TPU v9 imaginary")
    assert opcount.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
