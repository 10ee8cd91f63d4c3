"""Operations and bytes of a Llama-style decoder, from the shapes its
configuration file states, and the table of chip peaks.

Only useful work counts: a padding row or position does none, and work
done twice (recomputation) counts once.  A matrix product of an (m, k)
by a (k, n) operand is 2*m*k*n operations.
"""

from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['chips'])}")
    return table["chips"][device_kind]


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    return d, hq, hkv, dh, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that multiply each token (q, k, v, o and the
    three SwiGLU matrices)."""
    d, hq, hkv, dh, f, _, _ = _dims(cfg)
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f


def param_count(cfg: dict) -> int:
    """Every parameter: embedding, untied head, per-layer matrices, biases
    and norm scales, the final norm."""
    d, hq, hkv, dh, _, v, n = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d
    if cfg.get("model_type") == "qwen2" or cfg.get("attention_bias"):
        per_layer += hq * dh + 2 * hkv * dh
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return v * d + head + n * per_layer + d


def attention_ops(cfg: dict, q_pos_sum: int) -> float:
    """Score and value products of ``sum over query positions of the
    number of keys each attends to`` (= its position + 1, causal)."""
    _, hq, _, dh, _, _, n = _dims(cfg)
    return 4.0 * hq * dh * q_pos_sum * n


def prefill_ops(cfg: dict, prompt_len: int) -> float:
    """Useful operations of one prompt's prefill: every layer over every
    real token, causal attention, and the LM head at the last token only
    (a prefill returns one row of logits)."""
    d, _, _, _, _, v, n = _dims(cfg)
    L = int(prompt_len)
    return (2.0 * layer_matmul_params(cfg) * n * L
            + attention_ops(cfg, L * (L + 1) // 2)
            + 2.0 * d * v)


def decode_ops(cfg: dict, live_rows: int, context_sum: int) -> float:
    """Useful operations of decode steps that advanced ``live_rows``
    requests by one token each, whose contexts (tokens attended, the new
    one included) sum to ``context_sum``."""
    d, _, _, _, _, v, n = _dims(cfg)
    return ((2.0 * layer_matmul_params(cfg) * n + 2.0 * d * v) * live_rows
            + attention_ops(cfg, context_sum))
