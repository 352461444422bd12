"""Operations a training step requires and bytes the EF kernels must move.

Counted from the configuration's shapes, never from the program, so that no
program change can move the yardstick.

Required FLOPs of one token's forward pass, per layer: the four attention
projections, causal attention scores and values (on average half the
context is visible), and either the SwiGLU MLP or the router plus the k
chosen experts; then the LM head over the real vocabulary. Training is three
forward passes' worth (forward, and twice that backward). Not counted:
the embedding gather, norms and softmaxes, remat recompute, MoE one-hot
dispatch/combine and capacity padding, and the padded vocabulary rows.
"""

from __future__ import annotations


def forward_flops_per_token(m: dict, seq: int) -> float:
    """``m`` holds the configuration file's keys (published names)."""
    d = m["hidden_size"]
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f = m["intermediate_size"]
    proj = 2 * d * (2 * hq * hd + 2 * hkv * hd)
    scores = 2 * 2 * hq * hd * (seq / 2)
    if m.get("num_local_experts"):
        e, k = m["num_local_experts"], m["num_experts_per_tok"]
        ffn = 2 * d * e + k * 3 * 2 * d * f
    else:
        ffn = 3 * 2 * d * f
    head = 2 * d * m["vocab_size"]
    return m["num_hidden_layers"] * (proj + scores + ffn) + head


def train_flops_per_step(m: dict, tokens: int, seq: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq) * tokens


# ---------------------------------------------------------------------------
# HBM bytes of the bucket kernels (kernels/ef_sign.py), per call
# ---------------------------------------------------------------------------


def stats_bytes(nb: int, bs: int) -> float:
    """Per-bucket L1/L2 of g + e: read g and e, write two (nb,) columns."""
    return 2 * 4 * nb * bs + 2 * 4 * nb


def compress_bytes(nb: int, bs: int) -> float:
    """Sign, pack and residual: read g, e and the scales; write the words
    (one bit an element) and the new residual."""
    return 2 * 4 * nb * bs + 4 * nb + nb * bs / 8 + 4 * nb * bs


def decompress_mean_bytes(nb: int, bs: int, world: int) -> float:
    """Mean of W payloads: read W words and scales, write the (nb, bs) mean."""
    return world * (nb * bs / 8 + 4 * nb) + 4 * nb * bs


def param_count(m: dict) -> int:
    """Parameters the program holds for the configuration: the vocabulary
    padded to a multiple of 256, norms included, the head tied or not."""
    d, hq, hkv, hd = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f = m["intermediate_size"]
    attn = d * hq * hd * 2 + 2 * d * hkv * hd
    if m.get("num_local_experts"):
        e = m["num_local_experts"]
        ffn = e * 3 * d * f + d * e
    else:
        ffn = 3 * d * f
    vocab = -(-m["vocab_size"] // 256) * 256
    emb = vocab * d * (1 if m["tie_word_embeddings"] else 2)
    return m["num_hidden_layers"] * (attn + ffn + 2 * d) + d + emb


def n_buckets(m: dict, bucket_size: int) -> int:
    return -(-param_count(m) // bucket_size)
