"""Model FLOPs of a dense decoder-only language model, from its
configuration file (``chipbench/configs/<config>.json``, keys as in the
model's published ``config.json``).

``params`` copies the dense-decoder arithmetic of the program's
``repro.launch.roofline.count_params`` and ``train_flops`` its
``model_flops(..., "train")`` (6 N per token);
``chipbench/tests/test_copies.py`` shows that they agree today."""

from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // heads
    return d, heads, cfg["num_key_value_heads"], hd


def params(cfg: dict) -> float:
    """Matrix parameters, the embedding counted once (twice when the head
    is untied); norms and biases left out, as the program's count does."""
    d, heads, kv, hd = _dims(cfg)
    v, ff, layers = cfg["vocab_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    total = v * d * (1 if cfg.get("tie_word_embeddings") else 2)
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mlp = 3 * d * ff  # SwiGLU: gate, up, down
    return float(total + layers * (attn + mlp))


def train_flops(cfg: dict, tokens: int) -> float:
    """6 N per token: forward and backward through every matrix."""
    return 6.0 * params(cfg) * tokens


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6 N, plus attention's scores and weighted sums over the sequence in
    forward and backward (12 L d_attn seq). A tied head is one matrix
    multiplication of the forward pass, counted once in N; the embedding
    lookup is none. Recomputation (remat, the curvature refresh's second
    gradient pass) is not model work and is not counted."""
    d, heads, _, hd = _dims(cfg)
    return 6.0 * params(cfg) + 12.0 * cfg["num_hidden_layers"] * heads * hd * seq
