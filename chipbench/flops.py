"""Operations and bytes the algorithms need, computed from shapes.

Model FLOPs count the matrix multiplications a step needs: projections,
the MLP, the output head where logits are needed, and attention scores and
values over the positions a query may see (causal attention counts the
lower triangle). Recomputation, masking of padded blocks and the padded
vocabulary are not counted, so a share of a peak computed from these counts
is a lower bound of what the device did.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    return d, hq, hkv, dh


def attn_params(cfg: dict) -> int:
    d, hq, hkv, dh = _dims(cfg)
    return 2 * d * hq * dh + 2 * d * hkv * dh


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one decoder layer (attention and gated MLP)."""
    return attn_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


# ------------------------------------------------------------ dense decoder


def prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """One prefill of ``batch`` prompts: every layer over every position,
    causal attention, and the head at the last position only."""
    d, hq, _, dh = _dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_seq = (2 * L * layer_params(cfg) * prompt
               + L * 4 * hq * dh * prompt * (prompt + 1) // 2
               + 2 * d * V)
    return float(batch * per_seq)


def decode_flops(cfg: dict, batch: int, cache_len: int) -> float:
    """One decode step writing position ``cache_len``: the new token attends
    to ``cache_len + 1`` positions."""
    d, hq, _, dh = _dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    return float(batch * (2 * L * layer_params(cfg)
                          + L * 4 * hq * dh * (cache_len + 1)
                          + 2 * d * V))


def decode_bytes(cfg: dict, batch: int, cache_len: int, weight_bytes: int = 2,
                 kv_bytes: int = 2) -> float:
    """Bytes one decode step needs to move: every layer weight and norm, the
    output head, the batch's embedding rows, the valid KV prefix and the new
    KV row."""
    d, _, hkv, dh = _dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    weights = L * (layer_params(cfg) + 2 * d) + d + V * d + batch * d
    kv = batch * L * 2 * hkv * dh * (cache_len + 1)
    return float(weights * weight_bytes + kv * kv_bytes)


# ------------------------------------------------------- encoder-decoder


def encdec_forward_flops(cfg: dict, seq: int) -> float:
    """Forward FLOPs of one example: the encoder over its frames
    (bidirectional), the decoder over ``seq`` targets (causal self-attention,
    cross-attention over every frame), and logits at every target."""
    d, hq, hkv, dh = _dims(cfg)
    F, V = cfg["max_source_positions"], cfg["vocab_size"]
    Le, Ld, ff = cfg["encoder_layers"], cfg["decoder_layers"], cfg["intermediate_size"]
    mlp = 3 * d * ff
    enc = Le * (2 * F * (attn_params(cfg) + mlp) + 4 * hq * dh * F * F)
    self_attn = Ld * (2 * seq * (attn_params(cfg) + mlp) + 4 * hq * dh * seq * (seq + 1) // 2)
    cross = Ld * (2 * seq * 2 * d * hq * dh + 2 * F * 2 * d * hkv * dh + 4 * hq * dh * seq * F)
    head = 2 * seq * d * V
    return float(enc + self_attn + cross + head)


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward (twice the forward) of one step."""
    return 3.0 * batch * encdec_forward_flops(cfg, seq)
