"""Device peaks and the work of the served model, counted from shapes.

Peaks are keyed by ``device_kind`` as JAX reports it; an unknown device
is an error, never a default.  Source: Google Cloud documentation, "TPU
v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip).

The work functions count what the configuration's arithmetic needs for
the tokens actually prefilled and generated, at the configuration's
dtype, independent of how the program implements it: a later change of
pool dtype, padding or kernel moves a share, never the count.
"""
from __future__ import annotations

import math

PEAKS = {
    # device_kind: (bf16 FLOP/s, HBM bytes/s, source)
    "TPU v5 lite": (197e12, 819e9, "Google Cloud documentation, TPU v5e"),
}


def peaks(device_kind: str):
    """(FLOP/s, bytes/s) of one chip of this kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to perfbench/peaks.py with its source")
    f, b, _ = PEAKS[device_kind]
    return f, b


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def matmul_params(m: dict) -> int:
    """Weights one token passes through in the decoder stack (no
    embedding gather, no LM head)."""
    d, f = m["d_model"], m["d_ff"]
    hd = _hd(m)
    per_kind = {}
    attn = d * m["num_heads"] * hd * 2 + 2 * d * m["num_kv_heads"] * hd
    mlp = 3 * d * f if m.get("mlp_type", "swiglu") != "none" else 0
    per_kind["attn"] = per_kind["local"] = attn + mlp
    if m.get("ssm"):
        inner = m["ssm"]["expand"] * d
        st = m["ssm"]["state_size"]
        r = max(1, math.ceil(d / 16))
        per_kind["hymba"] = attn + mlp + d * 2 * inner \
            + inner * (r + 2 * st) + r * inner + inner * d
    H = m["num_heads"]
    per_kind["mlstm"] = d * H * hd * 4 + 2 * d * H + H * hd * d
    per_kind["slstm"] = d * 4 * d + d * d
    pat = m["layer_pattern"]
    reps = m["num_layers"] // len(pat)
    return reps * sum(per_kind[k] for k in pat)


def token_flops(m: dict, ctx: int) -> float:
    """FLOPs of one token at context length ``ctx`` (tokens it attends
    to, itself included) through the decoder stack: 2 per weight, plus
    attention scores and values over the live context (window-capped),
    plus the recurrent state updates."""
    hd = _hd(m)
    H = m["num_heads"]
    flops = 2.0 * matmul_params(m)
    pat = m["layer_pattern"]
    reps = m["num_layers"] // len(pat)
    win = m.get("sliding_window")
    for k in pat:
        if k == "attn":
            flops += reps * 4.0 * ctx * H * hd
        elif k in ("local", "hymba"):
            flops += reps * 4.0 * min(ctx, win or ctx) * H * hd
        if k == "hymba":
            inner = m["ssm"]["expand"] * m["d_model"]
            flops += reps * 6.0 * inner * m["ssm"]["state_size"]
        elif k == "mlstm":
            flops += reps * 6.0 * H * hd * hd
        elif k == "slstm":
            flops += reps * 12.0 * m["d_model"]
    return flops


def head_flops(m: dict) -> float:
    """LM head for one token whose logits are read."""
    return 2.0 * m["d_model"] * m["vocab_size"]


def request_flops(m: dict, prefilled: int, prompt_len: int,
                  generated: int) -> float:
    """FLOPs of one request: the ``prefilled`` tokens that ended its
    prompt (a prompt of ``prompt_len``; a forked prefix is not
    prefilled again) plus ``generated`` tokens, of which all but the
    last were fed back through a decode step.  One LM head per prompt
    and per decode step."""
    total = 0.0
    for pos in range(prompt_len - prefilled, prompt_len):
        total += token_flops(m, pos + 1)
    for j in range(max(0, generated - 1)):
        total += token_flops(m, prompt_len + j + 1)
    return total + head_flops(m) * max(1, generated)


def prefix_flops(m: dict, prefix_len: int) -> float:
    """FLOPs of prefilling a shared context prefix once."""
    return sum(token_flops(m, pos + 1) for pos in range(prefix_len))


def paged_kv_bytes(m: dict, prompt_len: int, generated: int,
                   itemsize: int = 2) -> float:
    """Least bytes a paged decode read moves for one request: the live
    K and V of every full-attention layer, at the model's dtype, for
    each decode step (context = prompt + tokens so far)."""
    n_attn = m["num_layers"] // len(m["layer_pattern"]) \
        * sum(1 for k in m["layer_pattern"] if k == "attn")
    per_tok = n_attn * 2 * m["num_kv_heads"] * _hd(m) * itemsize
    steps = max(0, generated - 1)
    # contexts prompt+1 .. prompt+steps
    ctx_sum = steps * prompt_len + steps * (steps + 1) / 2
    return per_tok * ctx_sum
