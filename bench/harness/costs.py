"""Operations the work needs, from shapes: the benchmark's own
copies, so that no change to the program can move the yardstick.

Counts are of the useful work of a dense train step: forward and
backward over every parameter a token uses and causal attention, from the
configuration file's ``model`` block (published key names, as
``harness/system.py`` reads them), layer kind by layer kind:

- attention: q, k, v and output projections (with biases where
  ``attention_bias``), or latent attention's ``w_q``, ``w_dkv``, ``w_ukv``
  and ``w_o``;
- MLP: two matrices for GELU, three for SwiGLU (``hidden_act: silu``);
- an expert layer: the router over the published experts, the shared
  experts, and the routed experts held.  A token uses
  ``num_experts_per_tok`` routed experts of the published
  ``n_routed_experts``; where the file holds a share of them (its
  ``model.n_routed_experts`` under ``published.n_routed_experts``), this
  chip computes that share of them: ``num_experts_per_tok x held /
  published``.
"""
from __future__ import annotations


def _m(config: dict) -> dict:
    return config["model"]


def _experts(config: dict) -> tuple[int, int]:
    """``(held, published)`` routed experts of each expert layer."""
    held = _m(config)["n_routed_experts"]
    return held, config.get("published", {}).get("n_routed_experts", held)


def _mla(m: dict) -> bool:
    return "kv_lora_rank" in m


def _head_dims(config: dict) -> tuple[int, int]:
    """``(d_qk, d_v)``: a head's query/key and value widths."""
    m = _m(config)
    if _mla(m):
        return m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    hd = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    return hd, hd


def _attention_params(config: dict) -> int:
    m = _m(config)
    d, h = m["hidden_size"], m["num_attention_heads"]
    if _mla(m):
        r, rd = m["kv_lora_rank"], m["qk_rope_head_dim"]
        nd, vd = m["qk_nope_head_dim"], m["v_head_dim"]
        return (d * h * (nd + rd) + d * (r + rd) + r * h * (nd + vd)
                + h * vd * d)
    hd = _head_dims(config)[0]
    kv = m.get("num_key_value_heads", h)
    n = 2 * d * h * hd + 2 * d * kv * hd
    if m.get("attention_bias", False):
        n += h * hd + 2 * kv * hd + d
    return n


def _mlp_params(config: dict, width: int) -> int:
    m = _m(config)
    return (3 if m["hidden_act"] == "silu" else 2) * m["hidden_size"] * width


def _expert_layer_params(config: dict) -> tuple[int, int]:
    """``(outside, per_expert)``: an expert layer's router and shared
    experts, and one routed expert."""
    m = _m(config)
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    shared = m.get("n_shared_experts") or 0
    return d * _experts(config)[1] + 3 * d * f * shared, 3 * d * f


def _layer_counts(config: dict) -> tuple[int, int]:
    """``(dense, expert)`` layers."""
    m = _m(config)
    n = m["num_hidden_layers"]
    if "n_routed_experts" not in m:
        return n, 0
    dense = m.get("first_k_dense_replace", 0)
    return dense, n - dense


def param_count(config: dict) -> int:
    """All parameters, a tied embedding counted once."""
    m = _m(config)
    d, V = m["hidden_size"], m["vocab_size"]
    norm = d if m.get("norm", "layernorm") == "rmsnorm" else 2 * d
    dense, expert = _layer_counts(config)
    n = (dense + expert) * (_attention_params(config) + 2 * norm)
    n += dense * _mlp_params(config, m["intermediate_size"])
    if expert:
        outside, per = _expert_layer_params(config)
        n += expert * (outside + _experts(config)[0] * per)
    return n + V * d + norm + (0 if m["tie_word_embeddings"] else d * V)


def active_param_count(config: dict) -> float:
    """Parameters one token's work uses on this chip: of the routed
    experts held, ``num_experts_per_tok x held / published``."""
    n = param_count(config)
    dense, expert = _layer_counts(config)
    if not expert:
        return n
    held, published = _experts(config)
    k = _m(config)["num_experts_per_tok"]
    return n - expert * _expert_layer_params(config)[1] * (
        held - k * held / published)


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """6 N_active (forward and backward over every parameter a token
    uses, the tied head once) + 3 L H (d_qk + d_v) S for causal
    attention's scores and values (with a window w under S, 2 w - w^2 / S
    in place of S)."""
    m = _m(config)
    dqk, dv = _head_dims(config)
    w = m.get("sliding_window")
    span = seq_len if w is None or w >= seq_len else 2 * w - w * w / seq_len
    attn = 3.0 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * (dqk + dv) * span
    return 6.0 * active_param_count(config) + attn
