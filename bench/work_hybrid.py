"""Operations and bytes the Mamba-2 + GQA hybrid's serving steps need, from
the model's shapes and the real counts (rows, tokens, cache positions),
never from what the implementation does.

``m`` is ``bench/kinds/lm_hybrid.model_dims``: int8 linears (one byte a
weight), a bf16 tied head, a float32 SSM state (n_mamba, H, P, N) and a
bf16 conv window (n_mamba, K-1, C) per slot, a bf16 KV cache in the
attention layers only.  SSM work is the sequential recurrence's: per
state element and token, decay, input and output (2 + 2 + 2 ops: the
dual chunked form the program runs for a prompt does more, and that
extra is not useful work).
"""
from __future__ import annotations

BF16, F32 = 2, 4
SSM_OPS_PER_ELEMENT = 6


def counts(m: dict):
    """(mamba layers, attention layers)."""
    return (m["layer_types"].count("mamba"),
            m["layer_types"].count("attention"))


def linear_params(m: dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    H, P, N = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    di = H * P
    nm, na = counts(m)
    mlp = 3 * d * f
    return (nm * (d * (2 * di + 2 * N + H) + di * d + mlp)
            + na * (2 * d * q + 2 * d * kv + mlp))


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"]


def state_elements(m: dict) -> int:
    """SSM state elements of one slot, over every mixer."""
    return counts(m)[0] * m["ssm_heads"] * m["ssm_head_dim"] * m["ssm_state"]


def conv_bytes(m: dict) -> int:
    """Conv window bytes of one slot, over every mixer."""
    C = m["ssm_heads"] * m["ssm_head_dim"] + 2 * m["ssm_state"]
    return counts(m)[0] * (m["conv_kernel"] - 1) * C * BF16


def kv_bytes_per_token(m: dict) -> int:
    return counts(m)[1] * 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def ssm_update_bytes(m: dict, rows: int) -> int:
    """Least HBM traffic of one decode step's state updates over ``rows``
    live slots: each slot's state read and written once, plus the step's
    inputs (x, dt, B, C) and output y, in float32."""
    H, P, N = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]
    per_layer = (2 * H * P + H + 2 * N) * F32
    return rows * (2 * F32 * state_elements(m) + counts(m)[0] * per_layer)


def _attn_ops(m: dict, pairs: float) -> float:
    return 4.0 * counts(m)[1] * m["n_heads"] * m["head_dim"] * pairs


def decode_step_work(m: dict, rows: int, live_positions: int):
    """(int8 ops, float ops, bytes) of one decode step of ``rows`` live
    rows whose KV rings hold ``live_positions`` positions in all: weights
    read once, each row's state and conv window read and written, the
    live KV read once."""
    int8_ops = 2.0 * (linear_params(m) + head_params(m)) * rows
    float_ops = (_attn_ops(m, live_positions)
                 + SSM_OPS_PER_ELEMENT * state_elements(m) * rows)
    nbytes = (linear_params(m) + head_params(m) * BF16
              + ssm_update_bytes(m, rows) + 2 * conv_bytes(m) * rows
              + kv_bytes_per_token(m) * live_positions)
    return int8_ops, float_ops, nbytes


def prefill_work(m: dict, n: int):
    """(int8 ops, float ops, bytes) of prefilling one prompt of ``n`` real
    tokens: every linear over n tokens, one row of logits, causal
    attention, the recurrence over n positions; weights read once, the
    state, conv window and n tokens of KV written once."""
    int8_ops = 2.0 * linear_params(m) * n + 2.0 * head_params(m)
    float_ops = (_attn_ops(m, n * (n + 1) / 2)
                 + SSM_OPS_PER_ELEMENT * state_elements(m) * n)
    nbytes = (linear_params(m) + head_params(m) * BF16
              + F32 * state_elements(m) + conv_bytes(m)
              + kv_bytes_per_token(m) * n)
    return int8_ops, float_ops, nbytes


def useful_ops(m: dict, generated_tokens: int, attended_pairs: float) -> float:
    """Useful decode work: 2 ops per weight and per head weight for each
    generated token, attention over the real (query, key) pairs, and the
    state update of each generated token."""
    return (2.0 * (linear_params(m) + head_params(m)) * generated_tokens
            + _attn_ops(m, attended_pairs)
            + SSM_OPS_PER_ELEMENT * state_elements(m) * generated_tokens)
