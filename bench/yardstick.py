"""The yardstick: chip peaks and the operations and bytes of the work the
benchmark asks for, computed from shapes alone.

Peaks of one chip, keyed by JAX's ``device_kind``. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect. A kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 200e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: the attention projections,
    the SwiGLU MLP of every layer, and the output head (the embedding
    lookup is a gather, not a product). ``m`` is a model's config keys."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    per_layer = d * hq * hd * 2 + d * hkv * hd * 2 + 3 * d * f
    return m["num_hidden_layers"] * per_layer + d * m["vocab_size"]


def token_flops(m: dict, context: int) -> float:
    """Model FLOPs of one token that attends ``context`` positions
    (itself included): 2 per weight, plus QK^T and PV over the context."""
    attn = 4 * context * m["num_attention_heads"] * m["head_dim"]
    return 2.0 * matmul_params(m) + m["num_hidden_layers"] * attn


def prefill_flops(m: dict, n: int) -> float:
    """Model FLOPs of prefilling an ``n``-token prompt (causal: token i
    attends i + 1 positions; the output head runs for the last one only)."""
    d, vocab = m["hidden_size"], m["vocab_size"]
    weights = 2.0 * (matmul_params(m) - d * vocab) * n + 2.0 * d * vocab
    attn = (m["num_hidden_layers"] * 4 * m["num_attention_heads"]
            * m["head_dim"] * n * (n + 1) / 2)
    return weights + attn


def kv_bytes_per_position(m: dict, itemsize: int = 2) -> int:
    """K and V of one position, one layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def paged_attn_call_cost(m: dict, contexts, itemsize: int = 2):
    """(FLOPs, bytes) one paged decode attention call needs for one layer:
    every row reads the K/V of its valid positions once, reads its query
    and writes its output. ``contexts`` are the rows' valid lengths."""
    hq, hd = m["num_attention_heads"], m["head_dim"]
    kv = kv_bytes_per_position(m, itemsize)
    n = sum(contexts)
    flops = 4.0 * hq * hd * n
    nbytes = kv * n + 2 * len(contexts) * hq * hd * itemsize
    return flops, nbytes
