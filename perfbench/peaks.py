"""Device peaks and the work of the served model, counted from shapes.

Peaks are keyed by ``device_kind`` as JAX reports it; an unknown device
is an error, never a default.  Source: Google Cloud documentation, "TPU
v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip).

The work functions count what the configuration's arithmetic needs for
the tokens actually prefilled and generated, at the configuration's
dtype, independent of how the program implements it: a later change of
pool dtype, padding or kernel moves a share, never the count.  Each
layer's share of the count is its kind's module's in ``perfbench/arch/``;
a kind that no module declares is an error, never a count of 0.
"""
from __future__ import annotations

from perfbench import arch

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


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def _layers(m: dict):
    """(kind, repeats) for each slot of the layer pattern, each kind
    known to ``perfbench/arch/``."""
    pat = m["layer_pattern"]
    reps = m["num_layers"] // len(pat)
    return [(arch.kind(k), reps) for k in pat]


def matmul_params(m: dict) -> int:
    """Weights one token passes through in the decoder stack (no
    embedding gather, no LM head)."""
    return sum(reps * k.params(m) for k, reps in _layers(m))


def token_flops(m: dict, ctx: int) -> float:
    """FLOPs of one token at context length ``ctx`` (tokens it attends
    to, itself included) through the decoder stack: 2 per weight, plus
    what each layer's kind counts beyond that -- attention scores and
    values over the live context (window-capped), recurrent state
    updates."""
    return _per_token(m)(ctx)


def _per_token(m: dict):
    """``token_flops`` of ``m`` as a function of the context, with the
    layers looked up once."""
    layers = _layers(m)
    weights = 2.0 * sum(reps * k.params(m) for k, reps in layers)

    def at(ctx: int) -> float:
        flops = weights
        for k, reps in layers:
            flops += reps * k.flops(m, ctx)
        return flops
    return at


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
    at = _per_token(m)
    total = 0.0
    for pos in range(prompt_len - prefilled, prompt_len):
        total += at(pos + 1)
    for j in range(max(0, generated - 1)):
        total += at(prompt_len + j + 1)
    return total + head_flops(m) * max(1, generated)


def prefix_flops(m: dict, prefix_len: int) -> float:
    """FLOPs of prefilling a shared context prefix once."""
    at = _per_token(m)
    return sum(at(pos + 1) for pos in range(prefix_len))


def paged_kv_bytes(m: dict, prompt_len: int, generated: int,
                   itemsize: int = 2) -> float:
    """Least bytes a paged decode read moves for one request: what each
    layer's kind reads through the paged pool per token of context (the
    live K and V of a full-attention layer), at the model's dtype, for
    each decode step (context = prompt + tokens so far)."""
    per_tok = sum(reps * k.kv_bytes(m, itemsize) for k, reps in _layers(m))
    steps = max(0, generated - 1)
    # contexts prompt+1 .. prompt+steps
    ctx_sum = steps * prompt_len + steps * (steps + 1) / 2
    return per_tok * ctx_sum
