"""``bench/counts.py`` against values worked out by hand."""

import json
import pathlib

from bench import counts

ROOT = pathlib.Path(__file__).resolve().parents[2]
OLMO = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())["model"]


def test_olmo_1b_dense_flops_per_token():
    # per layer: q, k, v, o = 4 x 2048^2 = 16,777,216 weights; SwiGLU
    # 3 x 2048 x 8192 = 50,331,648; 16 layers = 1,073,741,824; head
    # 2048 x 50304 = 103,022,592; 2 operations per weight
    assert counts.dense_flops_per_token(OLMO) == 2 * (1_073_741_824
                                                      + 103_022_592)


def test_olmo_1b_token_at_position():
    # attention at position 999 reads 1000 keys: 4 x 16 layers x 2048 x 1000
    assert counts.token_flops(OLMO, 999) == (
        counts.dense_flops_per_token(OLMO) + 4 * 16 * 2048 * 1000)


def test_causal_pairs():
    # 3 queries at positions 5, 6, 7 see 6 + 7 + 8 keys
    assert counts.causal_pairs(5, 3) == 21
    assert counts.causal_pairs(0, 64) == 64 * 65 // 2


def test_chunk_at_an_offset():
    # 64 queries at offset 1024 see 64 x 1024 + 2080 = 67,616 keys in all:
    # scores and weighted values, 4 x 16 layers x 2048 per (query, key)
    head = 2 * 2048 * 50304
    assert counts.chunk_flops(OLMO, 1024, 64) == (
        64 * (counts.dense_flops_per_token(OLMO) - head) + head
        + 4 * 16 * 2048 * 67_616)


def test_chunk_flops_counts_one_head():
    m = dict(OLMO)
    one = counts.chunk_flops(m, 0, 1)
    assert one == counts.token_flops(m, 0)
    # 64 tokens: 64 bodies, one vocabulary head, causal attention
    head = 2 * 2048 * 50304
    assert counts.chunk_flops(m, 0, 64) == (
        64 * (counts.dense_flops_per_token(m) - head) + head
        + 4 * 16 * 2048 * counts.causal_pairs(0, 64))


def test_dot_bytes():
    assert counts.dot_bytes(1 << 27) == 2 * 4 * 134_217_728
