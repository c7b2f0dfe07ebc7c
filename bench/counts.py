"""Operations and bytes that a call needs, from its shapes alone.

These count the work the algorithm requires, whatever implements it: a
change that pads, recomputes or reads the cache twice does not raise
them. A multiply-add counts as 2 operations.
"""

from __future__ import annotations

from typing import Any, Dict


def dot_bytes(n: int, itemsize: int = 4) -> int:
    """Both operands read once."""
    return 2 * n * itemsize


def dense_flops_per_token(m: Dict[str, Any]) -> int:
    """The matrix products of one token through the whole model: q, k,
    v, o, the SwiGLU's three, and the tied vocabulary head."""
    d, f, L = m["d_model"], m["d_ff"], m["n_layers"]
    dh = d // m["n_heads"]
    attn_proj = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * dh \
        + m["n_heads"] * dh * d
    return 2 * (L * (attn_proj + 3 * d * f) + d * m["vocab_size"])


def attention_flops(m: Dict[str, Any], pairs: int) -> int:
    """Scores and the probability-weighted values over ``pairs`` (query,
    key) pairs, every layer and head."""
    return 4 * m["n_layers"] * m["d_model"] * pairs


def causal_pairs(offset: int, n: int) -> int:
    """(query, key) pairs of ``n`` queries at positions offset..offset+n-1
    that attend causally to every key at or before them."""
    return n * offset + n * (n + 1) // 2


def token_flops(m: Dict[str, Any], position: int) -> int:
    """One token at ``position`` (attending position + 1 keys)."""
    return dense_flops_per_token(m) + attention_flops(m, position + 1)


def chunk_flops(m: Dict[str, Any], offset: int, n: int) -> int:
    """A prefill chunk of ``n`` valid tokens at ``offset``: each token's
    dense products, and causal attention over the cache before it. Only
    the last position needs the vocabulary head."""
    head = 2 * m["d_model"] * m["vocab_size"]
    return n * (dense_flops_per_token(m) - head) + head \
        + attention_flops(m, causal_pairs(offset, n))
