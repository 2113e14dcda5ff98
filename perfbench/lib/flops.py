"""Model FLOPs per trained token of a dense decoder, from its sizes.

6 * N_matmul + 12 * L * d * k_mean, where N_matmul counts every weight
matrix of the layers and the LM head (biases, norm scales and the embedding
lookup are not products), d is the attention width (heads * head size) and
k_mean is the mean number of keys a query sees under the causal and window
mask.  Recomputation under remat is not counted.
"""

from __future__ import annotations


def mean_keys(seq: int, window: int) -> float:
    """Mean over positions i = 0..seq-1 of min(i + 1, window or seq)."""
    w = window or seq
    full = min(w, seq)
    # positions 0..full-1 see i+1 keys; the rest see w
    return (full * (full + 1) / 2 + (seq - full) * w) / seq


def matmul_params(m: dict) -> int:
    D, L, H, K, F, V = (m["d_model"], m["n_layers"], m["n_heads"],
                        m["n_kv_heads"], m["d_ff"], m["vocab_size"])
    hd = m["head_dim"] or D // H
    gated = m["act"] in ("swiglu", "geglu")
    layer = D * H * hd * 2 + D * K * hd * 2 + D * F * (3 if gated else 2)
    return L * layer + D * V


def per_token(m: dict, seq: int) -> float:
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    attn = 12 * m["n_layers"] * m["n_heads"] * hd * mean_keys(
        seq, m["sliding_window"])
    return 6 * matmul_params(m) + attn
