"""The yardstick's arithmetic: the chip's published peaks, and the
operations and bytes an algorithm needs, computed from shapes. Kept with the
benchmark so that no change to the program can move them."""

from __future__ import annotations

#: one chip, keyed by ``device_kind`` as JAX reports it. Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s, 16 GB HBM.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks on record for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}): a device that is not in the table is "
            f"an error, not a default")
    return PEAKS[device_kind]


def attention_flops_per_token(cfg: dict, seq: int, *, causal: bool = True,
                              backward: bool = True) -> float:
    """QK^T and PV over one layer stack for one token of a ``seq``-token
    sequence: 2 matmuls of 2 * head_dim * keys FLOPs per head, keys being
    (seq + 1) / 2 on average under a causal mask. Backward costs twice the
    forward. Packed documents attend to less; the whole-sequence count is
    what the kernel is launched over, and what is counted here."""
    keys = (seq + 1) / 2 if causal else seq
    fwd = cfg["n_layers"] * cfg["n_heads"] * 2 * 2 * cfg["head_dim"] * keys
    return fwd * (3 if backward else 1)


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul for every token: the layers'
    projections and the output head; not the embedding table (a lookup)."""
    d, h, kv, hd, ff = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    layer = d * hd * (h + 2 * kv) + h * hd * d + 3 * d * ff
    return cfg["n_layers"] * layer + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward, attention included, recomputation not:
    6 FLOPs per matmul parameter plus attention's."""
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)


def decode_step_bytes(param_bytes: int, kv_bytes_per_token: int,
                      resident_tokens: float) -> float:
    """What one decode round has to read: every weight once, and the keys
    and values of the context the resident rows hold."""
    return param_bytes + kv_bytes_per_token * resident_tokens
