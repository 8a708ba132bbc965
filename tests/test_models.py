"""Model-level correctness: decode == teacher-forced forward, chunked
mLSTM == sequential, MoE dropless consistency, cache semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.models import Model, ssm
from repro.models.cache import (full_kv_positions, rolling_kv_positions,
                                take_cycle, put_cycle, write_seq,
                                write_token)


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma2-9b", "hymba-1.5b",
                                  "xlstm-350m", "whisper-base",
                                  "qwen2-moe-a2.7b"])
def test_decode_matches_forward(arch, key):
    cfg = get_smoke_config(arch)
    cf = float(cfg.moe.num_experts) if cfg.moe else 1.25
    m = Model(cfg, moe_capacity_factor=cf)
    params = m.init_params(key, max_seq=64)
    B, S, P = 2, 12, 8
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    batch = {"tokens": toks, "positions": pos}
    if cfg.is_encoder_decoder:
        batch["encoder_frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    full, _ = m.forward(params, batch)
    cache = m.init_cache(B, 32, jnp.float32)
    lg, cache = m.prefill(params, dict(batch, tokens=toks[:, :P],
                                       positions=pos[:, :P]), cache)
    errs = [float(jnp.abs(lg - full[:, P - 1]).max())]
    for t in range(P, S):
        lg, cache = m.decode_step(params, toks[:, t:t + 1], cache)
        errs.append(float(jnp.abs(lg - full[:, t]).max()))
    assert max(errs) < 1e-4, errs


def test_mlstm_chunked_equals_sequential(key):
    cfg = get_smoke_config("xlstm-350m")
    p = ssm.init_mlstm(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 37, cfg.d_model), jnp.float32)
    y1, st1 = ssm.mlstm_forward(p, x, cfg)
    y2, st2 = ssm.mlstm_forward_chunked(p, x, cfg, chunk=8)
    assert float(jnp.abs(y1 - y2).max()) < 1e-5
    for k in ("C", "n", "m"):
        assert float(jnp.abs(st1[k] - st2[k]).max()) < 1e-5


def test_mamba_step_matches_forward(key):
    cfg = get_smoke_config("hymba-1.5b")
    p = ssm.init_mamba(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 9, cfg.d_model), jnp.float32)
    y_full, _ = ssm.mamba_forward(p, x, cfg)
    state = ssm.mamba_init_state(cfg, 2, jnp.float32)
    outs = []
    for t in range(9):
        y, state = ssm.mamba_step(p, x[:, t:t + 1], cfg, state)
        outs.append(y)
    y_steps = jnp.concatenate(outs, axis=1)
    assert float(jnp.abs(y_full - y_steps).max()) < 1e-5


def test_moe_capacity_drops_are_bounded(key):
    """With cf=1.0 some tokens drop but output stays finite and the set
    of unrouted tokens only shrinks the output norm."""
    from repro.models.moe import apply_moe, init_moe
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model), jnp.float32)
    y_tight, aux1 = apply_moe(p, x, cfg, capacity_factor=1.0)
    y_loose, aux2 = apply_moe(p, x, cfg, capacity_factor=float(
        cfg.moe.num_experts))
    assert not bool(jnp.isnan(y_tight).any())
    assert float(jnp.linalg.norm(y_tight)) <= float(
        jnp.linalg.norm(y_loose)) * 1.05
    assert float(aux1) >= 0 and float(aux2) >= 0


# ---------------------------------------------------------------- cache


@given(st.integers(1, 200), st.integers(1, 64))
@settings(max_examples=40, deadline=None)
def test_rolling_positions_properties(length, window):
    pos = rolling_kv_positions(jnp.asarray(length), window)
    pos = [int(p) for p in pos]
    valid = [p for p in pos if p >= 0]
    # each valid slot j holds the latest position < length with p%W==j
    for j, p in enumerate(pos):
        if p >= 0:
            assert p % window == j and p < length
            assert p + window >= length   # latest such position
    # number of valid slots = min(length, window)
    assert len(valid) == min(length, window)


@given(st.integers(0, 100), st.integers(1, 128))
@settings(max_examples=30, deadline=None)
def test_full_positions_properties(length, smax):
    pos = [int(p) for p in full_kv_positions(jnp.asarray(length), smax)]
    for i, p in enumerate(pos):
        if i < min(length, smax):
            assert p == i
        else:
            assert p == -1


def test_write_token_cycle_indexed():
    """write_token touches exactly one (cycle, pos % L) slot of the
    stacked buffers and leaves everything else bit-identical."""
    nc, B, L, KV, hd = 3, 2, 4, 1, 2
    kv = {"k": jnp.arange(nc * B * L * KV * hd, dtype=jnp.float32
                          ).reshape(nc, B, L, KV, hd),
          "v": jnp.zeros((nc, B, L, KV, hd), jnp.float32)}
    tok = jnp.full((B, 1, KV, hd), 7.0)
    pos = jnp.asarray(5, jnp.int32)                  # 5 % 4 == slot 1
    out = write_token(kv, tok, tok, pos, jnp.asarray(1, jnp.int32))
    ref_k = np.asarray(kv["k"]).copy()
    ref_k[1, :, 1] = 7.0
    assert np.array_equal(np.asarray(out["k"]), ref_k)
    assert np.asarray(out["v"])[1, :, 1].min() == 7.0
    assert np.asarray(out["v"]).sum() == 7.0 * B * KV * hd


def test_write_seq_wraps_rolling_buffer():
    """A prefill segment longer than the rolling buffer keeps the last L
    tokens with slot j holding position p, p % L == j — only in the
    target cycle."""
    nc, B, L, KV, hd = 2, 1, 4, 1, 1
    kv = {"k": jnp.zeros((nc, B, L, KV, hd), jnp.float32),
          "v": jnp.zeros((nc, B, L, KV, hd), jnp.float32)}
    S = 6                                            # positions 0..5
    seg = jnp.arange(S, dtype=jnp.float32).reshape(B, S, KV, hd)
    out = write_seq(kv, seg, seg, jnp.asarray(0, jnp.int32),
                    jnp.asarray(1, jnp.int32))
    got = np.asarray(out["k"])[1, 0, :, 0, 0]
    # kept positions 2..5; slot j holds the position with p % 4 == j
    assert got.tolist() == [4.0, 5.0, 2.0, 3.0]
    assert np.asarray(out["k"])[0].sum() == 0.0      # other cycle untouched


def test_take_put_cycle_roundtrip():
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 2, 2)}
    cyc = jnp.asarray(2, jnp.int32)
    sl = take_cycle(tree, cyc)
    assert sl["a"].shape == (2, 2)
    back = put_cycle(tree, {"a": sl["a"] + 100.0}, cyc)
    assert np.asarray(back["a"])[2].min() == 108.0
    assert np.array_equal(np.asarray(back["a"])[:2], np.asarray(tree["a"])[:2])
