"""Operations and bytes the decoder's work needs, from shapes alone.

``m`` is a configuration file's ``model`` section: a decoder of
``num_layers`` blocks of multi-head attention (q/k/v biases, no output
bias) and an MLP (two matrices, or three when gated), with logits tied to
the embedding or from a separate head. A multiply-add counts as two
operations. Model FLOPs count the work the result needs: no padding, no
recomputation, logits only where they are used.
"""
from __future__ import annotations

from typing import Iterable


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def layer_matmul_params(m: dict) -> int:
    d, hd = m["d_model"], _hd(m)
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    mlp = (3 if m.get("mlp_gated") else 2) * d * m["d_ff"]
    return attn + mlp


def param_count(m: dict) -> int:
    """Every parameter of the program's decoder tree."""
    d, hd = m["d_model"], _hd(m)
    per_layer = layer_matmul_params(m) + 2 * d
    if m.get("qkv_bias"):
        per_layer += (m["num_heads"] + 2 * m["num_kv_heads"]) * hd
    head = 0 if m.get("tie_embeddings") else d * m["vocab_size"]
    return m["num_layers"] * per_layer + m["vocab_size"] * d + d + head


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Key and value bytes one token keeps, over all layers."""
    return 2 * m["num_layers"] * m["num_kv_heads"] * _hd(m) * itemsize


def token_flops(m: dict, context: int, logits: bool = True) -> int:
    """Forward operations for one token that attends to ``context`` keys
    (itself included)."""
    d, L = m["d_model"], m["num_layers"]
    attn_scores = 2 * 2 * m["num_heads"] * _hd(m) * context   # QK^T and AV
    f = L * (2 * layer_matmul_params(m) + attn_scores)
    if logits:
        f += 2 * d * m["vocab_size"]
    return f


def prefill_flops(m: dict, prompt_len: int) -> int:
    """A prompt's forward with logits at its last position only."""
    total = sum(token_flops(m, c, logits=False)
                for c in range(1, prompt_len + 1))
    return total + 2 * m["d_model"] * m["vocab_size"]


def decode_flops(m: dict, contexts: Iterable[int]) -> int:
    """One decode step, one token for each live row at its context."""
    return sum(token_flops(m, c) for c in contexts)


def decode_bytes(m: dict, contexts: Iterable[int], itemsize: int = 2) -> int:
    """Least HBM traffic of one decode step: every weight once (the
    embedding as the tied logits matrix) and each live row's keys and
    values once."""
    return param_count(m) * itemsize \
        + kv_bytes_per_token(m, itemsize) * sum(contexts)
