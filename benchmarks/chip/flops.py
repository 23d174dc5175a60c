"""The yardstick's arithmetic: the chip's published peaks, and the operations
a model needs per token, computed from its shapes.

Operations are the matrix multiplications the algorithm requires: two per
multiply-add. An embedding lookup is a gather and counts nothing; norms,
activations and the softmax are left out (well under 1% at these widths).
Training counts forward plus backward as three forwards. Recomputation
(remat) is work the chip does and the model does not need, so it does not
count."""

from __future__ import annotations

# One chip. Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s). A device kind that is not here
# is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to flops.PEAKS with its source (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def bert_forward_flops_per_token(c: dict, seq_len: int, n_layers: int) -> float:
    """``c`` holds the published keys (``hidden_size`` ...). Full attention:
    every token scores all ``seq_len`` keys."""
    d, f = c["hidden_size"], c["intermediate_size"]
    layer = 2 * (4 * d * d + 2 * d * f) + 4 * seq_len * d
    head = (2 * d * d + 2 * d * c.get("num_labels", 2)) / seq_len  # pooler + classifier, per sequence
    return n_layers * layer + head


def llama_forward_flops_per_token(c: dict, seq_len: int, n_layers: int) -> float:
    """Causal attention: a token at position p scores p + 1 keys, so the mean
    over a sequence is ``(seq_len + 1) / 2``."""
    d, f = c["hidden_size"], c["intermediate_size"]
    dq = c["num_attention_heads"] * c["head_dim"]
    dkv = c["num_key_value_heads"] * c["head_dim"]
    layer = 2 * (d * dq + 2 * d * dkv + dq * d + 3 * d * f) + 4 * dq * (seq_len + 1) / 2
    return n_layers * layer + 2 * d * c["vocab_size"]


def train_flops_per_token(forward_flops_per_token: float) -> float:
    return 3.0 * forward_flops_per_token


def mfu_percent(tokens_per_s: float, flops_per_token: float, device_kind: str, chips: int) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peaks(device_kind)["bf16_flops_per_s"])
