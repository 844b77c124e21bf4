"""Operations and bytes the algorithm needs, from the model's shapes and the
real token counts, never from what the implementation happens to do: a
change that drops padding or a plane walk divides the same work.

``m`` is the ``model`` block of ``bench/configs/<name>.json``; LM counts
are for a dense GQA decoder with a SwiGLU MLP and a tied head, stored in
int8 containers (one byte per linear weight) with a bf16 head and a bf16
KV cache.
"""
from __future__ import annotations

BF16 = 2


def lm_linear_params(m: dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return m["n_layers"] * (d * q + 2 * d * kv + q * d + 3 * d * f)


def lm_head_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"]


def kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def _attn_ops(m: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs, summed over layers."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * pairs


def decode_step_work(m: dict, rows: int, live_positions: int):
    """(ops, bytes) of one decode step of ``rows`` active rows whose
    caches hold ``live_positions`` positions in all: weights read once
    at container width, the live KV read once."""
    ops = (2.0 * (lm_linear_params(m) + lm_head_params(m)) * rows
           + _attn_ops(m, live_positions))
    nbytes = (lm_linear_params(m) + lm_head_params(m) * BF16
              + kv_bytes_per_token(m) * live_positions)
    return ops, nbytes


def prefill_work(m: dict, n: int):
    """(ops, bytes) of prefilling one prompt of ``n`` real tokens: every
    linear over n tokens, causal attention, one row of logits; weights
    read once, the KV of n tokens written once."""
    ops = (2.0 * lm_linear_params(m) * n + 2.0 * lm_head_params(m)
           + _attn_ops(m, n * (n + 1) / 2))
    nbytes = (lm_linear_params(m) + lm_head_params(m) * BF16
              + kv_bytes_per_token(m) * n)
    return ops, nbytes


def lm_useful_ops(m: dict, prompt_tokens: int, generated_tokens: int,
                  attended_pairs: float) -> float:
    """Useful LM work: 2 x parameters per real token (prompt and
    generated; the head once per generated token) plus attention over
    the real (query, key) pairs."""
    return (2.0 * lm_linear_params(m) * (prompt_tokens + generated_tokens)
            + 2.0 * lm_head_params(m) * generated_tokens
            + _attn_ops(m, attended_pairs))


def conv_out(h: int, k: int, stride: int, pad: int) -> int:
    return (h - k + 2 * pad) // stride + 1


def cnn_macs(layers: list) -> int:
    """Multiply-accumulates of one image through the layer table, at the
    input sizes it states (the sizes as served)."""
    total = 0
    for l in layers:
        if l["kind"] == "conv":
            ho = conv_out(l["hin"], l["hk"], l["stride"], l["pad"])
            total += ho * ho * l["hk"] * l["hk"] * l["cin"] * l["cout"]
        elif l["kind"] == "fc":
            total += l["cin"] * l["cout"]
    return total
