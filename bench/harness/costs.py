"""Operations the work needs, from shapes: the benchmark's own
copies, so that no change to the program can move the yardstick.

Counts are of the useful work of a dense train step: forward and
backward over every parameter and causal attention, from the published
shapes.
"""
from __future__ import annotations


def _m(config: dict) -> dict:
    return config["model"]


def matmul_shapes(config: dict) -> list[tuple[str, int, int]]:
    """Per-layer weight matmuls ``(name, k, o)``."""
    m = _m(config)
    d, h, kv, hd, f = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    return [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
            ("wo", h * hd, d), ("w_fc", d, f), ("w_proj", f, d)]


def param_count(config: dict) -> int:
    """All parameters, a tied embedding counted once."""
    m = _m(config)
    d, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    per = sum(k * o for _, k, o in matmul_shapes(config)) + 4 * d  # norms
    if m["attention_bias"]:
        per += sum(o for name, _, o in matmul_shapes(config)
                   if name in ("wq", "wk", "wv", "wo"))
    top = V * d + 2 * d + (0 if m["tie_word_embeddings"] else d * V)
    return L * per + top


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """6 N (forward and backward over every parameter, the tied head
    once) + 6 L H hd S for causal attention's scores and values."""
    m = _m(config)
    attn = 6.0 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * seq_len
    return 6.0 * param_count(config) + attn
