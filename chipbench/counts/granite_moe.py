"""Model FLOPs of serving the Granite MoE decoder, from the configuration's
sizes: 2 FLOPs per active weight per token computed (attention projections,
the router, the 8 chosen experts' SwiGLU), attention over the cache
(QKᵀ and PV, 4·H·hd per cached position), and the output logits (2·V·D) for
every position whose next token is produced. Only tokens the requests asked
for count: padding rows, idle slots and rejected draft positions are work
the system chose to do, not work the model needs.
"""
from __future__ import annotations


def active_weights(spec: dict) -> float:
    d = spec["hidden_size"]
    hd = spec["head_dim"]
    attn = d * hd * (2 * spec["num_attention_heads"] +
                     2 * spec["num_key_value_heads"])
    router = d * spec["num_local_experts"]
    experts = spec["num_experts_per_tok"] * 3 * d * spec["intermediate_size"]
    return spec["num_hidden_layers"] * (attn + router + experts)


def attention(spec: dict, context: float) -> float:
    return (spec["num_hidden_layers"] * 4.0 * spec["num_attention_heads"] *
            spec["head_dim"] * context)


def prompt(spec: dict, plen: int) -> float:
    """A prompt of plen tokens, and the logits of its last position."""
    return (2.0 * active_weights(spec) * plen +
            attention(spec, (plen + 1) / 2.0) * plen +
            2.0 * spec["vocab_size"] * spec["hidden_size"])


def output_token(spec: dict, context: int) -> float:
    """One generated token fed back at position `context`."""
    return (2.0 * active_weights(spec) + attention(spec, context + 1) +
            2.0 * spec["vocab_size"] * spec["hidden_size"])
