"""FLOPs that the work requires, from the published sizes alone.

A multiply-add counts 2.  Only what the result needs is counted: the
output head of a prefill for its last position alone, and attention over
the positions a token may see (causal).
Embedding lookups, norms, activations and softmaxes are left out (a few
per cent at these widths); so a share of the peak built on these counts
is a lower bound of the device's true rate.
"""
from __future__ import annotations

from typing import Any, Dict


def _attn_lm_layer_matmul(c: Dict[str, Any]) -> int:
    D, H, K = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, F = c["head_dim"], c["intermediate_size"]
    return 2 * (D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F)


def _rwkv_layer_matmul(c: Dict[str, Any]) -> int:
    D, F, r = c["hidden_size"], c["intermediate_size"], \
        c["decay_lora_rank"]
    timemix = 5 * D * D + 2 * D * r          # r, k, v, g, o; decay LoRA
    chanmix = 2 * D * F + D * D              # key, value, receptance
    return 2 * (timemix + chanmix)


def per_token(c: Dict[str, Any], context: float) -> float:
    """Forward FLOPs of one token that sees ``context`` positions (itself
    included), output head excluded."""
    L = c["num_hidden_layers"]
    if c["family"] == "qwen2":
        H, hd = c["num_attention_heads"], c["head_dim"]
        attn = 2 * 2 * H * hd * context       # scores and values
        return L * (_attn_lm_layer_matmul(c) + attn)
    if c["family"] == "rwkv6":
        H = c["num_attention_heads"]
        hd = c["hidden_size"] // H
        wkv = 2 * 2 * H * hd * hd             # state read-out and update
        return L * (_rwkv_layer_matmul(c) + wkv)
    raise ValueError(f"no FLOP count for family {c['family']!r}")


def head(c: Dict[str, Any]) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def _context_sum(c, start: int, n: int) -> float:
    """Sum over tokens start+1 .. start+n of the positions each sees."""
    if c["family"] != "qwen2":
        return float(n)
    return n * start + n * (n + 1) / 2.0


def prefill(c: Dict[str, Any], prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens, logits at its last position."""
    L_tok = per_token(c, 0.0) * prompt_len
    attn = per_token_attention(c) * _context_sum(c, 0, prompt_len)
    return L_tok + attn + head(c)


def per_token_attention(c: Dict[str, Any]) -> float:
    """Attention FLOPs per seen position (0 for an attention-free model)."""
    if c["family"] != "qwen2":
        return 0.0
    return c["num_hidden_layers"] * 2 * 2 * c["num_attention_heads"] \
        * c["head_dim"]


def decode(c: Dict[str, Any], context: int) -> float:
    """One decoded token that sees ``context`` live positions."""
    return per_token(c, 0.0) + per_token_attention(c) * context + head(c)

