"""Composable decoder (+optional encoder) model built from a ModelConfig.

The layer stack is organized as *pattern cycles*: the config's
``layer_pattern`` (e.g. ("local","attn") for Gemma-2, ("mlstm","slstm")
for xLSTM) is cycled num_layers/len(pattern) times.  Per-slot params are
stacked over cycles and the stack runs as one ``lax.scan`` over cycles,
keeping HLO size O(pattern) instead of O(layers) — essential for the
512-chip dry-run compile times.

Entry points (all pure functions of the param pytree):

  forward(params, batch)                 -> (logits, aux)   # train/eval
  prefill(params, batch, cache)          -> (last_logits, cache)
  decode_step(params, token, cache, ...) -> (logits, cache) # serve_step
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import cache as cache_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm


def _dt(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _capped_cycle_slice(kv_stack: dict, cycle, kv_cap):
    """The cycle's [B,L,KV,hd] K/V buffers, statically capped to the
    first ``kv_cap`` slots when the serving loop knows the live context
    can never reach past them (slot index <= absolute position for both
    full and not-yet-wrapped rolling buffers, so every dropped slot is
    masked anyway).  Keeps the decode read O(live context) instead of
    O(max_len)."""
    nc, B, L, KV, hd = kv_stack["k"].shape
    cap = L if kv_cap is None else min(kv_cap, L)
    start = (jnp.asarray(cycle, jnp.int32), jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32))

    def take(buf):     # one O(cap) slice, not an O(L) read then a crop
        return jax.lax.dynamic_slice(buf, start, (1, B, cap, KV, hd))[0]

    return take(kv_stack["k"]), take(kv_stack["v"])


class Model:
    def __init__(self, cfg: ModelConfig, moe_capacity_factor: float = 1.25,
                 ep_mesh=None):
        self.cfg = cfg
        # capacity factor for MoE dispatch; pass float(num_experts) for a
        # dropless guarantee (capacity == tokens*k), cheap at decode sizes.
        self.moe_cf = moe_capacity_factor
        # expert parallelism: pass the mesh to run MoE layers as
        # shard_map with expert-sharded weights (requires E % model == 0
        # — see distributed/expert_parallel.py); None = TP experts.
        self.ep_mesh = ep_mesh
        self.slots = cache_lib.slot_kinds(cfg)
        self.n_cycles = cache_lib.n_cycles(cfg)

    # ------------------------------------------------------------------ init

    def _init_block(self, key, kind: str, dtype, cross: bool, with_mlp: bool):
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p = {}
        if kind in ("attn", "local", "enc"):
            p["ln1"] = L.init_norm(cfg, dtype)
            p["attn"] = L.init_attention(ks[0], cfg, dtype)
        elif kind == "hymba":
            p["ln1"] = L.init_norm(cfg, dtype)
            p["attn"] = L.init_attention(ks[0], cfg, dtype)
            p["mamba"] = ssm.init_mamba(ks[1], cfg, dtype)
            p["bn_a"] = L.init_norm(cfg, dtype)   # per-branch output norms
            p["bn_m"] = L.init_norm(cfg, dtype)
        elif kind == "mlstm":
            p["ln1"] = L.init_norm(cfg, dtype)
            p["cell"] = ssm.init_mlstm(ks[0], cfg, dtype)
        elif kind == "slstm":
            p["ln1"] = L.init_norm(cfg, dtype)
            p["cell"] = ssm.init_slstm(ks[0], cfg, dtype)
        else:
            raise ValueError(kind)
        if cross:
            p["lnx"] = L.init_norm(cfg, dtype)
            p["xattn"] = L.init_attention(ks[2], cfg, dtype)
        if with_mlp and kind not in ("mlstm", "slstm") and cfg.mlp_type != "none":
            p["ln2"] = L.init_norm(cfg, dtype)
            if cfg.moe is not None:
                p["moe"] = moe_lib.init_moe(ks[3], cfg, dtype)
            else:
                p["mlp"] = L.init_mlp(ks[3], cfg, dtype)
        return p

    def init_params(self, key, max_seq: int = 2048) -> dict:
        cfg = self.cfg
        dtype = _dt(cfg)
        k_embed, k_blocks, k_head, k_enc, k_pos = jax.random.split(key, 5)
        params = {"embed": L.embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype)}
        if cfg.pos_embedding == "learned":
            params["pos_embed"] = L.embed_init(k_pos, max_seq, cfg.d_model, dtype)
        # decoder blocks, stacked over cycles
        blocks = {}
        slot_keys = jax.random.split(k_blocks, len(self.slots))
        for (name, kind), sk in zip(self.slots, slot_keys):
            cyc_keys = jax.random.split(sk, self.n_cycles)
            init_one = functools.partial(
                self._init_block, kind=kind, dtype=dtype,
                cross=cfg.is_encoder_decoder, with_mlp=True)
            blocks[name] = jax.vmap(init_one)(cyc_keys)
        params["blocks"] = blocks
        params["final_norm"] = L.init_norm(cfg, dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
        # encoder (whisper)
        if cfg.is_encoder_decoder:
            enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
            init_enc = functools.partial(self._init_block, kind="enc",
                                         dtype=dtype, cross=False, with_mlp=True)
            params["encoder"] = {
                "blocks": jax.vmap(init_enc)(enc_keys),
                "final_norm": L.init_norm(cfg, dtype),
            }
        return params

    # ------------------------------------------------------------- embedding

    def _embed(self, params, tokens, positions, vision_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.scale_embedding:
            x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        if vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        if cfg.pos_embedding == "learned":
            pos = positions if positions.ndim == 2 else positions[0]
            tbl = params["pos_embed"]
            x = x + tbl[jnp.clip(pos, 0, tbl.shape[0] - 1)]
        elif cfg.pos_embedding == "sinusoidal":
            pos = positions if positions.ndim == 2 else positions[0]
            x = x + L.sinusoidal_positions(int(pos.shape[-1]), cfg.d_model
                                           ).astype(x.dtype)[None]
        return x

    def _angles(self, positions, seq_len):
        cfg = self.cfg
        if cfg.pos_embedding != "rope":
            return None
        return L.rope_angles(positions, cfg.resolved_head_dim, cfg.rope_theta,
                             cfg.mrope_sections if cfg.use_mrope else ())

    # --------------------------------------------------------------- encoder

    def encode(self, params, frames: jax.Array) -> jax.Array:
        """frames: [B, enc_len, D] precomputed conv-frontend embeddings."""
        cfg = self.cfg
        B, S, _ = frames.shape
        x = frames.astype(_dt(cfg)) + L.sinusoidal_positions(
            S, cfg.d_model).astype(_dt(cfg))[None]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def body(x, p):
            h = L.apply_norm(p["ln1"], x, cfg)
            q, k, v = L.qkv_project(p["attn"], h, cfg, None)
            a = L.flash_attention(q, k, v, pos, pos, causal=False,
                                  q_block=min(512, S), kv_block=min(512, S))
            x = x + L.attention_out(p["attn"], a)
            h = L.apply_norm(p["ln2"], x, cfg)
            x = x + L.apply_mlp(p["mlp"], h, cfg)
            return x, None

        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        return L.apply_norm(params["encoder"]["final_norm"], x, cfg)

    # ---------------------------------------------------------- block bodies

    def _buffer_positions(self, kv_pos, batch, first, pos_shift):
        """Broadcast per-slot buffer positions to [B, L] and translate
        them into the query frame: with ``pos_shift`` (continuous
        batching) positions become per-row relative — slots before the
        row's first token go negative, i.e. invalid — otherwise slots
        left of ``first`` are masked to -1."""
        L_buf = kv_pos.shape[-1]
        kv_pos = jnp.broadcast_to(kv_pos, (batch, L_buf))
        if pos_shift is not None:
            return kv_pos - pos_shift[:, None]
        if first is not None:       # mask left-padding slots
            return jnp.where(kv_pos >= first[:, None], kv_pos, -1)
        return kv_pos

    @staticmethod
    def _positions_vec(start, L_buf, window):
        """Per-slot absolute positions for a buffer read with *per-row*
        lengths ``start`` [B] (paged mode) -> [B, L_buf]."""
        if window is not None and L_buf == window:
            return cache_lib.rolling_kv_positions(start[:, None], L_buf)
        return cache_lib.full_kv_positions(start[:, None], L_buf)

    def _cached_seq_attention(self, q, k, v, kv_stack, cycle, start, qpos,
                              window, first, pos_shift, ctx=None):
        """Chunk-mode attention: the segment's queries attend to (cached
        past ⊕ current segment), then the segment's K/V are persisted —
        so a prompt is absorbed pass by pass through static-shape
        programs (see ``prefill_chunk``).  Returns (attn, new_kv_stack).

        Paged mode (``ctx["paged"]``): ``start`` is per-row [B]; full
        "attn" slots live in the shared block pool and are read through
        the row's block table / written by absolute-position scatter;
        rolling slots keep the per-row buffer but index it per row
        (rows advance independently, so the shared-position write path
        would interleave them)."""
        cfg = self.cfg
        paged = ctx is not None and ctx.get("paged")
        B, S = q.shape[0], q.shape[1]
        if paged:
            # pads (qpos == -1) scatter nowhere; real tokens land at
            # their absolute position first + relative
            abs_write = jnp.where(qpos >= 0, qpos + pos_shift[:, None], -1)
            if window is None:
                tables = ctx["tables"]
                NB = tables.shape[1]
                bs = kv_stack["k"].shape[2]
                k_buf, v_buf = cache_lib.paged_gather_kv(
                    kv_stack, tables, cycle, NB)
                L_buf = NB * bs
                past = cache_lib.full_kv_positions(start[:, None], L_buf)
                new_kv = cache_lib.paged_write_seq(kv_stack, k, v,
                                                   abs_write, tables, cycle)
            else:
                k_buf, v_buf = _capped_cycle_slice(kv_stack, cycle, None)
                L_buf = k_buf.shape[1]
                past = self._positions_vec(start, L_buf, window)
                new_kv = cache_lib.rolling_write_seq(kv_stack, k, v,
                                                     abs_write, cycle)
            past = self._buffer_positions(past, B, None, pos_shift)
        else:
            k_buf, v_buf = _capped_cycle_slice(kv_stack, cycle, None)
            L_buf = k_buf.shape[1]
            if window is not None and L_buf == window:
                past = cache_lib.rolling_kv_positions(start, L_buf)
            else:
                past = cache_lib.full_kv_positions(start, L_buf)
            past = self._buffer_positions(past, B, first, pos_shift)
            new_kv = cache_lib.write_seq(kv_stack, k, v, start, cycle)
        k_all = jnp.concatenate([k_buf, k.astype(k_buf.dtype)], axis=1)
        v_all = jnp.concatenate([v_buf, v.astype(v_buf.dtype)], axis=1)
        kv_pos = jnp.concatenate([past, qpos], axis=1)
        a = L.flash_attention(q, k_all, v_all, qpos, kv_pos, causal=True,
                              window=window,
                              softcap=cfg.attn_logit_softcap,
                              q_block=min(512, S),
                              kv_block=min(512, L_buf + S))
        return a, new_kv

    def _paged_decode_attn(self, q, kv_stack, cycle, start, tables, nb_cap,
                           pos_shift, active=None, softcap=None):
        """Paged decode read for a pooled "attn" slot: write the token
        into its block (frozen rows scatter nowhere), then attend
        through the first ``nb_cap`` block-table columns via the paged
        attention kernel/oracle — O(live blocks), not O(max_len).
        ``start`` [B] is per-row; valid slots are first <= pos <= start
        (start is the just-written position).  A row with ``active``
        False reads nothing (its last position is -1) and gets zeros.
        Returns attn [B,1,H,hd]; the write happens in the caller (needs
        k/v)."""
        # view the cycle-stacked pool as one [nc*P, bs, KV, hd] pool and
        # offset the tables into the live cycle's stripe — extracting the
        # cycle slice would copy the whole pool every decode step
        nc, P = kv_stack["k"].shape[:2]
        k_pool = kv_stack["k"].reshape((nc * P,) + kv_stack["k"].shape[2:])
        v_pool = kv_stack["v"].reshape((nc * P,) + kv_stack["v"].shape[2:])
        tbl = tables[:, :nb_cap]
        tbl = jnp.where(tbl >= 0, tbl + cycle * P, -1)
        last = start if active is None else jnp.where(active, start, -1)
        a = kernel_ops.paged_decode_attention(
            q[:, 0], k_pool, v_pool, tbl,
            pos_shift, last, softcap=softcap)
        return a[:, None]

    def _attn_sublayer(self, p, x, kind, qpos, kpos, angles, kv_stack, mode,
                       start, cycle, first=None, kv_cap=None,
                       pos_shift=None, ctx=None):
        """Self-attention sublayer.  ``kv_stack`` holds the cycle-stacked
        KV buffers ([nc,B,L,KV,hd] leaves); writes land in cycle
        ``cycle``.  Returns (delta_x, new_kv_stack)."""
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg)
        q, k, v = L.qkv_project(p["attn"], h, cfg, angles)
        window = cfg.sliding_window if kind in ("local", "hymba") else None
        paged = ctx is not None and ctx.get("paged")
        if mode == "decode" and paged:
            # per-row positions: start [B] is each row's write position
            active = ctx.get("active")
            if window is None:
                new_kv = cache_lib.paged_write_token(
                    kv_stack, k, v, start, ctx["tables"], cycle, active)
                a = self._paged_decode_attn(
                    q, new_kv, cycle, start, ctx["tables"], ctx["nb_cap"],
                    pos_shift, active, softcap=cfg.attn_logit_softcap)
            else:
                new_kv = cache_lib.rolling_write_token(
                    kv_stack, k, v, start, cycle, active)
                k_buf, v_buf = _capped_cycle_slice(new_kv, cycle, None)
                kv_pos = self._positions_vec(start + 1, k_buf.shape[1],
                                             window)
                kv_pos = self._buffer_positions(kv_pos, x.shape[0], None,
                                                pos_shift)
                a = L.decode_attention(q, k_buf, v_buf, qpos[:, 0], kv_pos,
                                       window=window,
                                       softcap=cfg.attn_logit_softcap)
        elif mode == "decode":
            new_kv = cache_lib.write_token(kv_stack, k, v, start, cycle)
            k_buf, v_buf = _capped_cycle_slice(new_kv, cycle, kv_cap)
            L_buf = k_buf.shape[1]
            # a buffer is rolling iff it equals the window (i.e. smaller
            # than max context); otherwise slot index == absolute position
            # (a capped buffer cannot have wrapped yet, so the capped
            # read is index == position too)
            if window is not None and L_buf == window:
                kv_pos = cache_lib.rolling_kv_positions(start + 1, L_buf)
            else:
                kv_pos = cache_lib.full_kv_positions(start + 1, L_buf)
            kv_pos = self._buffer_positions(kv_pos, x.shape[0], first,
                                            pos_shift)
            a = L.decode_attention(q, k_buf, v_buf,
                                   qpos[:, 0], kv_pos,
                                   window=window, softcap=cfg.attn_logit_softcap)
        elif mode == "chunk":
            a, new_kv = self._cached_seq_attention(
                q, k, v, kv_stack, cycle, start, qpos, window, first,
                pos_shift, ctx=ctx)
        else:
            S = x.shape[1]
            a = L.flash_attention(
                q, k, v, qpos, kpos, causal=True, window=window,
                softcap=cfg.attn_logit_softcap,
                q_block=min(512, S), kv_block=min(512, S))
            new_kv = None
            if kv_stack is not None:  # prefill: persist roped K/V
                new_kv = cache_lib.write_seq(kv_stack, k, v, start, cycle)
        return L.attention_out(p["attn"], a), new_kv

    def _cross_sublayer(self, p, x, enc_out, enc_kv, mode):
        """Whisper cross-attention. enc_out used at prefill (computes K/V);
        enc_kv reused at decode."""
        cfg = self.cfg
        h = L.apply_norm(p["lnx"], x, cfg)
        B, Sq = h.shape[:2]
        hd = cfg.resolved_head_dim
        q = (h @ p["xattn"]["wq"]).reshape(B, Sq, cfg.num_heads, hd)
        if enc_kv is None:
            Se = enc_out.shape[1]
            k = (enc_out @ p["xattn"]["wk"]).reshape(B, Se, cfg.num_kv_heads, hd)
            v = (enc_out @ p["xattn"]["wv"]).reshape(B, Se, cfg.num_kv_heads, hd)
        else:
            k, v = enc_kv["k"], enc_kv["v"]
            Se = k.shape[1]
        pos_q = jnp.zeros((B, Sq), jnp.int32)
        pos_k = jnp.zeros((B, Se), jnp.int32)
        if Sq == 1:
            a = L.decode_attention(q, k, v, pos_q[:, 0], pos_k)
        else:
            a = L.flash_attention(q, k, v, pos_q, pos_k, causal=False,
                                  q_block=min(512, Sq), kv_block=min(512, Se))
        return L.attention_out(p["xattn"], a), {"k": k, "v": v}

    def _mlp_sublayer(self, p, x):
        cfg = self.cfg
        if "moe" in p:
            h = L.apply_norm(p["ln2"], x, cfg)
            if self.ep_mesh is not None:
                from repro.distributed.expert_parallel import \
                    apply_moe_expert_parallel
                y, aux = apply_moe_expert_parallel(
                    p["moe"], h, cfg, self.ep_mesh,
                    capacity_factor=self.moe_cf)
            else:
                y, aux = moe_lib.apply_moe(p["moe"], h, cfg,
                                           capacity_factor=self.moe_cf)
            return y, aux
        if "mlp" in p:
            h = L.apply_norm(p["ln2"], x, cfg)
            return L.apply_mlp(p["mlp"], h, cfg), 0.0
        return jnp.zeros_like(x), 0.0

    def _apply_block(self, p, x, kind, ctx, cache_stack, mode):
        """One layer.  ``cache_stack`` is the slot's *cycle-stacked* state
        (leading dim = nc) or None; reads slice cycle ``ctx["cycle"]``,
        writes go back into the stack through cycle-indexed
        ``dynamic_update_slice``.  Returns (x, new_cache_stack, aux)."""
        cfg = self.cfg
        aux = 0.0
        new_stack = None
        cyc = ctx.get("cycle")
        if kind in ("attn", "local"):
            da, new_kv = self._attn_sublayer(
                p, x, kind, ctx["qpos"], ctx["kpos"], ctx["angles"],
                cache_stack, mode, ctx["start"], cyc, ctx.get("first"),
                ctx.get("kv_cap"), ctx.get("pos_shift"), ctx=ctx)
            # checkpoint_name lets the remat policy SAVE this psum
            # output instead of re-all-reducing it in the backward
            # recompute (§Perf iteration 4)
            da = jax.ad_checkpoint.checkpoint_name(da, "sublayer_out")
            x = x + da
            new_stack = new_kv
        elif kind == "hymba":
            kv = {k: cache_stack[k] for k in ("k", "v")} if cache_stack else None
            h = L.apply_norm(p["ln1"], x, cfg)
            # attention branch (bypasses ln1 in _attn_sublayer; replicate here)
            q, k, v = L.qkv_project(p["attn"], h, cfg, ctx["angles"])
            if mode == "decode" and ctx.get("paged"):
                # per-row rolling write/read (rows advance independently)
                new_kv = cache_lib.rolling_write_token(
                    kv, k, v, ctx["start"], cyc, ctx.get("active"))
                k_buf, v_buf = _capped_cycle_slice(new_kv, cyc, None)
                kv_pos = self._buffer_positions(
                    self._positions_vec(ctx["start"] + 1, k_buf.shape[1],
                                        cfg.sliding_window),
                    x.shape[0], None, ctx.get("pos_shift"))
                a = L.decode_attention(q, k_buf, v_buf,
                                       ctx["qpos"][:, 0], kv_pos,
                                       window=cfg.sliding_window)
                mo, mstate = ssm.mamba_step(
                    p["mamba"], h, cfg,
                    cache_lib.take_cycle(cache_stack["mamba"], cyc))
            elif mode == "decode":
                new_kv = cache_lib.write_token(kv, k, v, ctx["start"], cyc)
                k_buf, v_buf = _capped_cycle_slice(new_kv, cyc,
                                                   ctx.get("kv_cap"))
                W = k_buf.shape[1]
                kv_pos = self._buffer_positions(
                    cache_lib.rolling_kv_positions(ctx["start"] + 1, W),
                    x.shape[0], ctx.get("first"), ctx.get("pos_shift"))
                a = L.decode_attention(q, k_buf, v_buf,
                                       ctx["qpos"][:, 0], kv_pos,
                                       window=cfg.sliding_window)
                mo, mstate = ssm.mamba_step(
                    p["mamba"], h, cfg,
                    cache_lib.take_cycle(cache_stack["mamba"], cyc))
            elif mode == "chunk":
                a, new_kv = self._cached_seq_attention(
                    q, k, v, kv, cyc, ctx["start"], ctx["qpos"],
                    cfg.sliding_window, ctx.get("first"),
                    ctx.get("pos_shift"), ctx=ctx)
                mo, mstate = ssm.mamba_forward(
                    p["mamba"], h, cfg,
                    cache_lib.take_cycle(cache_stack["mamba"], cyc),
                    mask=ctx.get("seq_mask"))
            else:
                S = x.shape[1]
                a = L.flash_attention(q, k, v, ctx["qpos"], ctx["kpos"],
                                      causal=True, window=cfg.sliding_window,
                                      q_block=min(512, S), kv_block=min(512, S))
                new_kv = cache_lib.write_seq(kv, k, v, ctx["start"], cyc) \
                    if kv else None
                mo, mstate = ssm.mamba_forward(
                    p["mamba"], h, cfg,
                    None if cache_stack is None
                    else cache_lib.take_cycle(cache_stack["mamba"], cyc))
            ao = L.attention_out(p["attn"], a)
            fused = 0.5 * (L.apply_norm(p["bn_a"], ao, cfg)
                           + L.apply_norm(p["bn_m"], mo, cfg))
            x = x + fused
            if cache_stack is not None:
                new_stack = dict(new_kv, mamba=cache_lib.put_cycle(
                    cache_stack["mamba"], mstate, cyc))
        elif kind in ("mlstm", "slstm"):
            h = L.apply_norm(p["ln1"], x, cfg)
            # chunkwise mLSTM for sequences: exact, MXU-shaped, and
            # O(S/chunk) backward snapshots (the per-step scan would
            # checkpoint the [B,H,hd,hd] matrix state EVERY step —
            # ~68 GiB/layer at 4k tokens; §Perf "beyond-paper" item 5)
            fwd = ssm.mlstm_forward_chunked if kind == "mlstm" \
                else ssm.slstm_forward
            step = ssm.mlstm_step if kind == "mlstm" else ssm.slstm_step
            state = None if cache_stack is None \
                else cache_lib.take_cycle(cache_stack, cyc)
            if mode == "decode":
                y, st = step(p["cell"], h, cfg, state)
            else:
                y, st = fwd(p["cell"], h, cfg, state,
                            mask=ctx.get("seq_mask"))
            x = x + y
            if cache_stack is not None:
                new_stack = cache_lib.put_cycle(cache_stack, st, cyc)
        else:
            raise ValueError(kind)
        # cross-attention (whisper decoder)
        if cfg.is_encoder_decoder:
            enc_kv = None if cache_stack is None or mode != "decode" \
                else cache_lib.take_cycle(ctx["enc_slice"], cyc)
            dx, enc_kv_new = self._cross_sublayer(p, x, ctx.get("enc_out"),
                                                  enc_kv, mode)
            x = x + dx
            if cache_stack is None:
                ctx["_enc_kv_new"] = enc_kv_new     # train: popped, discarded
            elif mode == "decode":
                ctx["_enc_kv_new"] = ctx["enc_slice"]   # read-only at decode
            else:
                ctx["_enc_kv_new"] = cache_lib.put_cycle(
                    ctx["enc_slice"], enc_kv_new, cyc)
        dm, aux = self._mlp_sublayer(p, x)
        dm = jax.ad_checkpoint.checkpoint_name(dm, "sublayer_out")
        x = x + dm
        return x, new_stack, aux

    # ------------------------------------------------------------- sequence

    def _run_stack(self, params, x, ctx, cache, mode, remat=False):
        """Scan the pattern-cycle stack. cache may be None (pure train).

        With a cache, the cycle-stacked slot buffers ride in the scan
        *carry* (not xs -> stacked ys, which re-materializes every
        stacked buffer each step): cycle i reads its slice and writes
        back through cycle-indexed ``dynamic_update_slice``, so XLA
        aliases the (donated) cache in place and the per-decode-step KV
        write is O(token) instead of an O(max_len) cache rebuild."""
        cfg = self.cfg
        have_cache = cache is not None

        def cycle_body(carry, xs):
            x, aux, slots = carry
            # pin the residual stream to (batch-sharded, D-replicated):
            # FSDP'd projections otherwise tempt XLA into resharding
            # activations to (batch-replicated, D-sharded) layouts
            from repro.distributed.sharding import maybe_constrain
            x = maybe_constrain(x, ("pod", "data"), None, None)
            blk_params, cycle = xs
            ctx["cycle"] = cycle
            new_slots = dict(slots)
            for name, kind in self.slots:
                cs = slots[name] if have_cache else None
                if cfg.is_encoder_decoder and have_cache:
                    ctx["enc_slice"] = slots["enc"]
                x, ns, a = self._apply_block(blk_params[name], x, kind, ctx,
                                             cs, mode)
                if have_cache:
                    new_slots[name] = ns
                aux = aux + a
            if cfg.is_encoder_decoder and have_cache:
                new_slots["enc"] = ctx.pop("_enc_kv_new")
            elif cfg.is_encoder_decoder:
                ctx.pop("_enc_kv_new", None)
            return (x, aux, new_slots), None

        # NOTE §Perf iteration 4 (refuted trade): a remat policy saving
        # the "sublayer_out" psum results cuts collectives another 12%
        # but costs +4 GiB/device (17.5 > 16 GiB HBM) — plain remat wins.
        body = jax.checkpoint(cycle_body) if remat else cycle_body
        slots0 = {}
        if have_cache:
            slots0 = dict(cache["slots"])
            if cfg.is_encoder_decoder:
                slots0["enc"] = cache["enc"]
        xs = (params["blocks"], jnp.arange(self.n_cycles, dtype=jnp.int32))
        (x, aux, slots), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), slots0), xs)
        new_cache = None
        if have_cache:
            enc = slots.pop("enc", None)
            new_cache = dict(cache, slots=slots)
            if enc is not None:
                new_cache["enc"] = enc
        return x, aux, new_cache

    def lm_head(self, params):
        return params["embed"].T if self.cfg.tie_embeddings \
            else params["lm_head"]

    def _logits(self, params, x):
        cfg = self.cfg
        x = L.apply_norm(params["final_norm"], x, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
        if cfg.final_logit_softcap:
            logits = (cfg.final_logit_softcap
                      * jnp.tanh(logits.astype(jnp.float32)
                                 / cfg.final_logit_softcap))
        return logits

    # ---------------------------------------------------------------- public

    def forward(self, params, batch: dict, remat: bool = False,
                return_features: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
        """Training/eval forward over a full sequence.

        batch: tokens [B,S], positions [B,S] (or [3,B,S] M-RoPE), optional
        vision_embeds [B,Nv,D] (prepended), encoder_frames [B,Se,D].
        Returns (logits [B,S_total,V], aux_loss scalar) — or the
        pre-head features [B,S_total,D] when return_features=True (the
        fused chunked cross-entropy consumes those directly).
        """
        cfg = self.cfg
        tokens, positions = batch["tokens"], batch["positions"]
        x = self._embed(params, tokens, positions,
                        batch.get("vision_embeds"))
        S = x.shape[1]
        pos2d = positions if positions.ndim == 2 else positions[0]
        ctx = {
            "qpos": pos2d, "kpos": pos2d,
            "angles": self._angles(positions, S),
            "start": jnp.zeros((), jnp.int32),
        }
        if cfg.is_encoder_decoder:
            ctx["enc_out"] = self.encode(params, batch["encoder_frames"])
        x, aux, _ = self._run_stack(params, x, ctx, None, "train", remat=remat)
        if return_features:
            x = L.apply_norm(params["final_norm"], x, cfg)
            return x, jnp.asarray(aux, jnp.float32)
        return self._logits(params, x), jnp.asarray(aux, jnp.float32)

    def init_cache(self, batch: int, max_len: int, dtype=None) -> dict:
        return cache_lib.init_cache(self.cfg, batch, max_len,
                                    dtype or _dt(self.cfg))

    def prefill(self, params, batch: dict, cache: dict
                ) -> Tuple[jax.Array, dict]:
        """Absorb a prompt; returns (last-position logits [B,V], cache)."""
        cfg = self.cfg
        tokens, positions = batch["tokens"], batch["positions"]
        x = self._embed(params, tokens, positions, batch.get("vision_embeds"))
        S = x.shape[1]
        pos2d = positions if positions.ndim == 2 else positions[0]
        ctx = {
            "qpos": pos2d, "kpos": pos2d,
            "angles": self._angles(positions, S),
            "start": cache["length"],
        }
        if cfg.is_encoder_decoder:
            ctx["enc_out"] = self.encode(params, batch["encoder_frames"])
        x, aux, cache = self._run_stack(params, x, ctx, cache, "prefill")
        cache["length"] = cache["length"] + S
        return self._logits(params, x[:, -1]), cache

    def prefill_chunk(self, params, batch: dict, cache: dict
                      ) -> Tuple[jax.Array, dict]:
        """Absorb one pass of prompt chunks ([B, S] tokens) into the
        cache.

        Like ``prefill`` but (a) queries attend to ALL cached K/V —
        earlier passes included — so a prompt padded to a multiple of
        the chunk C runs through static-shape passes: a frame's [B, C]
        program C tokens at a time, a staging prefill several chunks a
        pass (``ServeEngine.staging_passes``), (b) recurrent state
        updates are masked at pad positions (left-padding to a chunk
        multiple is numerically exact), and (c) ``batch["positions"]``
        are per-row *relative* — counted from the row's first real token
        (``cache["first"]``), -1 at pads — while cache slots stay keyed
        by the shared absolute ``cache["length"]``, so RoPE / learned
        position embeddings match an unpadded solo run regardless of
        where in a shared frame the row starts.  Returns
        (last-position logits [B,V], cache).

        Known redundancy: encoder-decoder configs re-run the encoder
        per pass (enc K/V are rewritten idempotently) — a static
        first-chunk flag would double the compile count, and the
        serving path feeds zero frames, so the repeated pass is cheap;
        revisit if real audio frames ever reach continuous serving."""
        cfg = self.cfg
        if cfg.pos_embedding == "sinusoidal":
            raise NotImplementedError(
                "sinusoidal embeddings ignore the chunk offset; chunked "
                "prefill is unsupported for pos_embedding='sinusoidal'")
        tokens, positions = batch["tokens"], batch["positions"]
        x = self._embed(params, tokens, positions,
                        batch.get("vision_embeds"))
        S = x.shape[1]
        pos2d = positions if positions.ndim == 2 else positions[0]
        ctx = {
            "qpos": pos2d, "kpos": pos2d,
            "angles": self._angles(positions, S),
            "start": cache["length"],
            "pos_shift": cache["first"],
            "seq_mask": pos2d >= 0,
        }
        if "block_tables" in cache:      # paged: per-row length [B]
            ctx["paged"] = True
            ctx["tables"] = cache["block_tables"]
        if cfg.is_encoder_decoder:
            ctx["enc_out"] = self.encode(params, batch["encoder_frames"])
        x, aux, cache = self._run_stack(params, x, ctx, cache, "chunk")
        cache["length"] = cache["length"] + S
        last_col = batch.get("last_col")
        if last_col is not None:
            # right-padded chunks (prefix-fork suffix): the row's last
            # real token sits at a per-row column, not column -1
            xl = x[jnp.arange(x.shape[0]), last_col]
        else:
            xl = x[:, -1]
        return self._logits(params, xl), cache

    def decode_step(self, params, token: jax.Array, cache: dict,
                    kv_cap: Optional[int] = None, relative: bool = False,
                    nb_cap: Optional[int] = None,
                    active: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, dict]:
        """token: [B,1] int32. One serve_step: logits for the next token.

        ``kv_cap`` (static) bounds the decode-side KV *read* when the
        caller knows positions never reach past it (the serving loop
        passes prompt_bucket + max_new_tokens): slots at index >= cap
        are always masked, so dropping them is exact while making the
        per-step read O(live context) instead of O(max_len).

        ``relative`` (static) switches positions to the per-row frame of
        ``prefill_chunk``: each row's position is its live token count
        (``length - first[row]``), and buffer slots before the row's
        first token go negative (invalid) instead of being masked by
        ``first`` — the continuous-batching decode mode.

        Paged caches (``"block_tables"`` present) carry per-row
        ``length`` [B]: pooled "attn" slots write into their block and
        read through the first ``nb_cap`` (static) block-table columns;
        rows with ``active`` False (finished) neither write nor advance
        their length, so one row's decode never disturbs another's
        position stream.  Requires ``relative=True``."""
        cfg = self.cfg
        B = token.shape[0]
        paged = "block_tables" in cache
        if paged and not relative:
            raise ValueError("paged decode_step requires relative=True")
        pos_scalar = cache["length"]
        if relative:
            pos = (pos_scalar - cache["first"])[:, None].astype(jnp.int32)
        else:
            pos = jnp.broadcast_to(pos_scalar, (B, 1)).astype(jnp.int32)
        if cfg.use_mrope:
            positions = jnp.broadcast_to(pos, (3, B, 1))
        else:
            positions = pos
        x = self._embed(params, token, positions)
        ctx = {
            "qpos": pos, "kpos": None,
            "angles": self._angles(positions, 1),
            "start": pos_scalar,
            "first": None if relative else cache.get("first"),
            "pos_shift": cache["first"] if relative else None,
            "kv_cap": kv_cap,
        }
        if paged:
            nb_total = cache["block_tables"].shape[1]
            ctx["paged"] = True
            ctx["tables"] = cache["block_tables"]
            ctx["nb_cap"] = nb_total if nb_cap is None \
                else min(nb_cap, nb_total)
            ctx["active"] = active
        x, _, cache = self._run_stack(params, x, ctx, cache, "decode")
        inc = 1 if active is None else active.astype(jnp.int32)
        cache = dict(cache, length=cache["length"] + inc)
        return self._logits(params, x[:, 0]), cache
