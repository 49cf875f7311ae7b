"""Operations and bytes the algorithms need, computed from shapes.

Dense decoder (the shapes of a ``bench/configs`` model file): a token at
position ``p`` (attending to ``p + 1`` positions) needs

    2 * matmul_params + 4 * layers * heads * head_dim * (p + 1)

operations, plus ``2 * d_model * vocab`` for the output head where its
logits are used (the last prompt position and every decode step).  A
decode tick must read every weight once and the live K/V rows of each
active slot.

Fixed-point operator: one application of a dense ``n x n`` float32
operator to the slots' iterates reads ``4 n^2`` bytes.
"""

from __future__ import annotations

from typing import Iterable


def layer_matmul_params(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    attn = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd + m["n_heads"] * hd * d
    return attn + 3 * d * m["d_ff"]


def n_params(m: dict) -> int:
    """All parameters: layers (with their two norms), the final norm and
    the embedding (tied to the output head unless the file says not)."""
    d = m["d_model"]
    emb = m["vocab"] * d * (1 if m.get("tie_embeddings", True) else 2)
    return m["n_layers"] * (layer_matmul_params(m) + 2 * d) + d + emb


def weight_bytes(m: dict) -> int:
    return n_params(m) * m["dtype_bytes"]


def kv_bytes_per_token(m: dict) -> int:
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * m["dtype_bytes"]


def token_flops(m: dict, pos: int, *, head: bool) -> float:
    """Operations for one token at position ``pos``."""
    f = 2.0 * m["n_layers"] * layer_matmul_params(m)
    f += 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * (pos + 1)
    if head:
        f += 2.0 * m["d_model"] * m["vocab"]
    return f


def span_flops(m: dict, start: int, stop: int, *, head_from: int) -> float:
    """Operations for the tokens at positions ``start .. stop - 1``, the
    output head counted from position ``head_from`` on (closed form of the
    sum of :func:`token_flops`)."""
    n = max(0, stop - start)
    if n == 0:
        return 0.0
    f = 2.0 * m["n_layers"] * layer_matmul_params(m) * n
    # sum of (pos + 1) for pos in [start, stop)
    f += 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * (
        (start + 1 + stop) * n / 2.0
    )
    f += 2.0 * m["d_model"] * m["vocab"] * max(0, stop - max(start, head_from))
    return f


def operator_bytes(n: int, dtype_bytes: int = 4) -> float:
    return float(dtype_bytes) * n * n
