"""Model FLOPs of the benchmark's dense decoder, from a configuration
file's published keys.  Recomputation is not counted, nor the embedding
lookup; a multiply-add counts 2.  Attention is causal: the token at
position ``i`` (from 0) attends to ``i + 1`` keys, costing ``4 * heads *
head_dim * (i + 1)`` per layer for the scores and the weighted values.
"""

from __future__ import annotations


def _sizes(config: dict) -> tuple[int, int, int, int]:
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim", d // heads)
    kv = config["num_key_value_heads"] * head_dim
    q = heads * head_dim
    block = d * q + 2 * d * kv + q * d + 3 * d * config["intermediate_size"]
    return block * config["num_hidden_layers"], d * config["vocab_size"], \
        q, config["num_hidden_layers"]


def attention_flops(config: dict, keys: int) -> int:
    """One token's attention over ``keys`` keys, all layers."""
    _blocks, _head, q, layers = _sizes(config)
    return 4 * q * keys * layers


def forward_flops_per_token(config: dict, seq: int) -> float:
    """Forward FLOPs per token of a causal sequence of ``seq`` tokens,
    logits at every position."""
    blocks, head, q, layers = _sizes(config)
    mean_keys = (seq + 1) / 2
    return 2 * (blocks + head) + 4 * q * mean_keys * layers


def train_flops_per_token(config: dict, seq: int) -> float:
    """Forward and backward: three times the forward."""
    return 3 * forward_flops_per_token(config, seq)


def prefill_flops(config: dict, prompt_len: int) -> int:
    """A prompt's pass through every block, logits at its last position
    only."""
    blocks, head, q, layers = _sizes(config)
    keys = prompt_len * (prompt_len + 1) // 2
    return 2 * blocks * prompt_len + 2 * head + 4 * q * keys * layers


def decode_flops(config: dict, position: int) -> int:
    """One decoded token at ``position`` (from 0): it attends to
    ``position + 1`` keys."""
    blocks, head, q, layers = _sizes(config)
    return 2 * (blocks + head) + 4 * q * (position + 1) * layers
