"""Batched serving engine: jit'd prefill + fully on-device decode loop.

This replaces the paper's vLLM backend with a JAX-native engine: a
preallocated cache (full / rolling-window / recurrent, per architecture)
and two compiled programs:

  prefill      — pads host-side in numpy, then one jitted program builds
                 positions + cache, absorbs the prompt batch, and samples
                 the first token
  decode loop  — a single ``jax.lax.while_loop`` that samples, writes
                 the output buffer, tracks per-row done flags and EOS,
                 and early-exits when every row has finished

There is no per-token host synchronization: ``generate`` dispatches two
compiled programs, then performs exactly one device->host transfer of
the [B, max_new_tokens] output buffer and per-row lengths.

Prompt batches are left-padded to a power-of-two *bucket* so the
prefill jit cache is reused across calls (the static-shape analogue of
continuous batching); the decode loop compiles once per (batch,
GenerationParams, prompt bucket) — the bucket enters as the static
``kv_cap`` that keeps the per-step KV read O(live context).
Architectures with recurrent state (mLSTM/sLSTM/hymba) absorb pad
embeddings into their state, so for those the batch is padded to the
exact max prompt length instead of a bucket — identical numerics to
unbucketed serving — and ``kv_cap`` is skipped (their KV, if any, sits
in window-sized buffers already, and a per-prompt-length static cap
would recompile the decode loop per length).

``generate_reference`` keeps the original per-token Python loop (one
host sync per token) for parity tests and the throughput benchmark.

Continuous batching (``prefill_chunk`` set): prompts are padded to a
multiple of C tokens and absorbed through ``Model.prefill_chunk``
instead of a per-bucket/per-length fused prefill — killing the
per-exact-prompt-length recompile on recurrent architectures — and
``ContinuousSession`` refills individual decode slots the moment a row
finishes (EOS / budget) by prefilling the next request into a
single-row staging cache and swapping it in with ``cache.insert_row``,
instead of waiting for the whole wave.  A frame's batch goes through
one static [B, C] program C tokens at a time; a staging prefill runs
its k·C tokens in passes of up to ``PREFILL_PASS_MAX`` tokens (see
``ServeEngine.staging_passes``).  See docs/ARCHITECTURE.md
("Continuous batching").
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import cache as cache_lib
from repro.models.model import Model
from repro.obs import trace as obs_trace
from repro.serving.sampling import GenerationParams, sample_token

_RECURRENT_KINDS = ("mlstm", "slstm", "hymba")
_MIN_BUCKET = 8

# Longest pass, in tokens, of a staging prefill (refill, prefix
# prefill).  A pass reads every weight once, so short passes are
# weight-read bound: on a TPU v5e (197 TFLOP/s bf16, 819 GB/s) the
# ridge point is ~240 FLOP/byte, and a bf16 matmul turns compute-bound
# at ~256 rows.  1024 rows sit 4x past the ridge, so the per-pass weight
# read and block-table gather are a small part of each pass, while
# activations stay O(1024 x d_ff) however long the context is.
PREFILL_PASS_MAX = 1024

# The layer of each jitted serving program, by the ``__name__`` of the
# function ``ServeEngine`` jits.  A profiler trace names each program
# execution ``jit_<__name__>(<fingerprint>)`` on its ``XLA Modules``
# line, so this table is how device time is read by layer.
PROGRAM_LAYERS = {
    "decode_step": "decode",
    "_decode_loop_impl": "decode",
    "_decode_cont_impl": "decode",
    "_prefill_sample_impl": "prefill",
    "_prefill_chunk_impl": "prefill",
    "_refill_impl": "prefill",
    "_paged_prefill_chunk_impl": "prefill",
    "_paged_refill_impl": "prefill",
    "_paged_prefix_prefill_impl": "prefill",
    "_fresh_cache_impl": "other",
    "_paged_fresh_cache_impl": "other",
    "_paged_copy_block_impl": "other",
}


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 512,
                 batch_size: int = 8, pad_id: int = 0,
                 moe_capacity_factor: Optional[float] = None,
                 prefill_chunk: Optional[int] = None,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None):
        cf = moe_capacity_factor
        if cf is None and cfg.moe is not None:
            cf = float(cfg.moe.num_experts)   # dropless at serving sizes
        self.model = Model(cfg, moe_capacity_factor=cf or 1.25)
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.batch_size = batch_size
        self.pad_id = pad_id
        # paged KV: full-attention K/V lives in a shared pool of
        # ``num_blocks`` blocks of ``block_size`` tokens addressed
        # through per-row block tables (see models/cache.py); rows then
        # carry independent lengths, so ContinuousSession admits
        # indefinitely instead of drain-and-restarting frames
        self.paged = bool(paged)
        self.block_size = int(block_size)
        if self.paged:
            if prefill_chunk is None:
                raise ValueError("paged=True rides the continuous path; "
                                 "build the engine with prefill_chunk=...")
            if block_size < 1:
                raise ValueError(f"block_size={block_size} must be >= 1")
            self.nb_total = cache_lib.num_row_blocks(max_len, block_size)
            # default pool: every row can hold a full-length context
            self.num_blocks = int(num_blocks) if num_blocks is not None \
                else batch_size * self.nb_total
            self._pooled = cache_lib.paged_slot_names(cfg)
            self._pooled_set = frozenset(self._pooled)
            self._nonpooled = [n for n, _ in self.model.slots
                               if n not in self._pooled_set]
            self._zero_state = None
        # recurrent state absorbs pad embeddings -> exact-length padding
        self._exact_length = any(kind in _RECURRENT_KINDS
                                 for _, kind in self.model.slots)
        # donate the cache: decode writes are cycle-indexed
        # dynamic_update_slice ops on the (scan/while_loop) carry, so XLA
        # updates the buffers in place — no decode-step cache copy
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,),
                               static_argnames=("kv_cap", "relative"))
        self._prefill_sample = jax.jit(self._prefill_sample_impl,
                                       static_argnames=("gp",))
        self._decode_loop = jax.jit(self._decode_loop_impl,
                                    static_argnames=("gp", "kv_cap"),
                                    donate_argnums=(2,))
        # continuous-batching programs (chunked prefill + refillable
        # decode); compiled shapes: [B, C] frame chunks, [1, C] staging
        # chunks, and the segment loop per (gp, pow2 kv_cap)
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk={prefill_chunk} must be "
                                 f">= 1")
            if cfg.pos_embedding == "sinusoidal":
                raise ValueError("chunked prefill is unsupported for "
                                 "pos_embedding='sinusoidal' (the table "
                                 "ignores the chunk offset)")
            # chunks a staging-prefill pass: the most that fit in
            # PREFILL_PASS_MAX and, where a layer is windowed, in the
            # window (a longer pass would overwrite its own rolling-
            # buffer writes).  MoE layers that can drop tokens keep one
            # chunk a pass: their expert capacity is counted per pass.
            g = PREFILL_PASS_MAX // prefill_chunk
            if any(kind in ("local", "hymba")
                   for _, kind in self.model.slots):
                g = min(g, cfg.sliding_window // prefill_chunk)
            if cfg.moe is not None \
                    and self.model.moe_cf < cfg.moe.num_experts:
                g = 1
            self._pass_chunks = max(1, g)
            self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                          donate_argnums=(2,))
            self._decode_cont = jax.jit(self._decode_cont_impl,
                                        static_argnames=("gp", "kv_cap",
                                                         "nb_cap"),
                                        donate_argnums=(2, 4, 5, 6, 7))
            # one fused dispatch per mid-frame refill: staging cache +
            # chunk scan + first-token sample + row swap + carry updates
            self._refill = jax.jit(self._refill_impl,
                                   static_argnames=("gp",),
                                   donate_argnums=(2, 3, 4, 5, 6))
            self._fresh_cache = jax.jit(self._fresh_cache_impl)
        if self.paged:
            self._paged_fresh_cache = jax.jit(self._paged_fresh_cache_impl)
            self._paged_prefill_chunk = jax.jit(
                self._paged_prefill_chunk_impl, donate_argnums=(2,))
            # unified mid-frame admission (plain + prefix fork): the
            # row_state snapshot (arg 11) is deliberately NOT donated —
            # a prefix entry's snapshot forks into many rows
            self._paged_refill = jax.jit(self._paged_refill_impl,
                                         static_argnames=("gp",),
                                         donate_argnums=(2, 3, 4, 5, 6))
            self._paged_prefix_prefill = jax.jit(
                self._paged_prefix_prefill_impl, donate_argnums=(2,))
            self._paged_copy_block = jax.jit(self._paged_copy_block_impl,
                                             donate_argnums=(0,))

    # ---------------------------------------------------------------- batching

    def max_prompt_len(self, max_new_tokens: int = 0) -> int:
        """Longest prompt the preallocated cache can hold while leaving
        room for ``max_new_tokens`` decode steps."""
        return max(1, self.max_len - max(0, max_new_tokens))

    def clip_prompts(self, prompts: List[List[int]], max_new_tokens: int
                     ) -> List[List[int]]:
        """Truncate-left any prompt longer than the cache allows (keeps
        the question-side suffix of RAG prompts) with a warning, instead
        of failing with a shape error inside jit."""
        cap = self.max_prompt_len(max_new_tokens)
        out, clipped = [], 0
        for p in prompts:
            if len(p) > cap:
                out.append(list(p)[-cap:])
                clipped += 1
            else:
                out.append(p)
        if clipped:
            warnings.warn(
                f"{clipped} prompt(s) exceeded max_len={self.max_len} - "
                f"max_new_tokens={max_new_tokens}; truncated-left to "
                f"{cap} tokens", stacklevel=3)
        return out

    def prompt_bucket(self, prompt_len: int, max_new_tokens: int = 0) -> int:
        """Padded prompt length for a request: the smallest power-of-two
        bucket >= prompt_len that still leaves room in the cache for
        ``max_new_tokens`` decode steps.  Exact-length for recurrent
        architectures (pads would perturb their state)."""
        if self._exact_length:
            # never a 0-length pad target (an all-empty wave would
            # otherwise build [B, 0] tokens and fail inside jit)
            return max(1, prompt_len)
        cap = max(prompt_len, self.max_len - max_new_tokens)
        b = _MIN_BUCKET
        while b < prompt_len:
            b *= 2
        return min(b, cap)

    def _pad_batch(self, prompts: List[List[int]], pad_to: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Left-pad to ``pad_to`` on the host (numpy: one device transfer
        instead of one dispatch per row).  Returns int32 (tokens [B,L],
        first-valid-position [B])."""
        B = self.batch_size
        assert len(prompts) <= B
        L = max(1, pad_to, max(len(p) for p in prompts))
        toks = np.full((B, L), self.pad_id, np.int32)
        first = np.full((B,), L, np.int32)     # unused rows: everything padded
        for i, p in enumerate(prompts):
            toks[i, L - len(p):] = p
            first[i] = L - len(p)
        return toks, first

    # ------------------------------------------------------- compiled programs

    def _prefill_sample_impl(self, params, toks, first, key,
                             gp: GenerationParams):
        """One program: positions + fresh cache + prefill + first sampled
        token.  Pad positions are marked -1 so attention masks them."""
        B, L = toks.shape
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        pos = jnp.where(pos >= first[:, None], pos, -1)
        if self.cfg.use_mrope:
            pos = jnp.broadcast_to(pos, (3, B, L))
        batch = {"tokens": toks, "positions": pos}
        if self.cfg.is_encoder_decoder:
            batch["encoder_frames"] = jnp.zeros(
                (B, self.cfg.encoder_seq_len, self.cfg.d_model), jnp.float32)
        cache = self.model.init_cache(B, self.max_len, jnp.float32)
        cache["first"] = first
        logits, cache = self.model.prefill(params, batch, cache)
        return sample_token(logits, gp, key, 0), cache

    def _decode_loop_impl(self, params, tok, cache, key, n_active,
                          gp: GenerationParams, kv_cap=None):
        """Compiled decode: carries (t, token, cache, done, out, count)
        through a ``while_loop``; exits early once all active rows are
        done.  Returns the [B, max_new] output buffer, per-row
        emitted-token counts, and the final cache — returned (and never
        copied back to host) so the donated input cache aliases it and
        the while_loop mutates the buffers in place."""
        B = tok.shape[0]
        max_new = gp.max_new_tokens
        out = jnp.zeros((B, max_new), jnp.int32)
        done = jnp.arange(B) >= n_active          # idle slots start done
        count = jnp.zeros((B,), jnp.int32)
        state = (jnp.zeros((), jnp.int32), tok, cache, done, out, count)

        def cond(st):
            t, _, _, done, _, _ = st
            return (t < max_new) & ~jnp.all(done)

        def body(st):
            t, tok, cache, done, out, count = st
            col = jnp.where(done, 0, tok[:, 0])
            out = jax.lax.dynamic_update_slice_in_dim(
                out, col[:, None], t, axis=1)
            count = count + jnp.where(done, 0, 1)
            if gp.eos_id is not None:
                done = done | (tok[:, 0] == gp.eos_id)

            def step(args):
                tok, cache = args
                logits, cache = self.model.decode_step(params, tok, cache,
                                                       kv_cap=kv_cap)
                return sample_token(logits, gp, key, t + 1), cache

            # skip the trailing decode when this was the last recorded
            # token (either the buffer is full or every row just hit EOS)
            tok, cache = jax.lax.cond(
                (t + 1 < max_new) & ~jnp.all(done), step,
                lambda args: args, (tok, cache))
            return (t + 1, tok, cache, done, out, count)

        _, _, cache, _, out, count = jax.lax.while_loop(cond, body, state)
        return out, count, cache

    # -------------------------------------------- continuous-batching programs

    def _fresh_cache_impl(self, first, length0):
        """A zeroed cache positioned at ``length0`` with per-row first
        valid positions ``first`` — the frame (batch) or staging
        (single-row) cache of a continuous session."""
        cache = self.model.init_cache(first.shape[0], self.max_len,
                                      jnp.float32)
        cache["first"] = first.astype(jnp.int32)
        cache["length"] = jnp.asarray(length0, jnp.int32)
        return cache

    def _chunk_step(self, params, toks, cache, l_end=None):
        """One [B, S] pass of the chunked prefill (a frame's C-token
        chunk, or a staging pass of several chunks): derive per-row
        RELATIVE positions (counted from ``cache['first']``, -1 at pads)
        at the cache's current absolute offset, then
        ``Model.prefill_chunk``.  The offset is traced, so every pass of
        every prompt length reuses one compiled program per shape.
        ``l_end`` (paged caches: per-row lengths, right-padded chunk
        tails) additionally masks columns at/after the prompt end and
        points the logits read at the last real column."""
        B, S = toks.shape
        first = cache["first"]
        abs_pos = jnp.reshape(cache["length"], (-1, 1)) \
            + jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = abs_pos >= first[:, None]
        if l_end is not None:
            valid = valid & (abs_pos < l_end)
        pos = jnp.where(valid, abs_pos - first[:, None], -1)
        if self.cfg.use_mrope:
            pos = jnp.broadcast_to(pos, (3, B, S))
        batch = {"tokens": toks, "positions": pos}
        if l_end is not None:
            batch["last_col"] = jnp.clip(
                l_end - 1 - jnp.reshape(cache["length"], (-1,)), 0, S - 1)
        if self.cfg.is_encoder_decoder:
            batch["encoder_frames"] = jnp.zeros(
                (B, self.cfg.encoder_seq_len, self.cfg.d_model),
                jnp.float32)
        return self.model.prefill_chunk(params, batch, cache)

    def _prefill_chunk_impl(self, params, toks, cache):
        return self._chunk_step(params, toks, cache)

    def _refill_impl(self, params, toks, tok, cache, done, remaining, idx,
                     slot, p_len, budget, key, gp: GenerationParams):
        """Fused mid-frame refill — ONE dispatch per slot swap: prefill
        ``toks`` ([1, k*C], left-padded) into a fresh staging
        cache whose frames end at the live cache's position, sample the
        row's first token, ``insert_row`` the staging state into
        ``slot``, and flip the slot's decode carry (done / remaining /
        idx) live.  Compiled once per chunk count k."""
        d = cache["length"]
        staging = self._fresh_cache_impl((d - p_len)[None],
                                         d - toks.shape[1])
        logits, staging = self._staging_prefill(params, toks, staging)
        tok_new = sample_token(logits, gp, key, 0)
        cache = cache_lib.insert_row(cache, staging, jnp.int32(0), slot)
        tok = jax.lax.dynamic_update_slice(tok, tok_new, (slot, 0))
        done = jax.lax.dynamic_update_slice(
            done, jnp.zeros((1,), done.dtype), (slot,))
        remaining = jax.lax.dynamic_update_slice(
            remaining, budget[None].astype(remaining.dtype), (slot,))
        idx = jax.lax.dynamic_update_slice(
            idx, jnp.zeros((1,), idx.dtype), (slot,))
        return tok, cache, done, remaining, idx

    # ------------------------------------------------- paged-KV programs

    def _paged_fresh_cache_impl(self, first, lengths, tables):
        """A zeroed paged cache with per-row first positions, lengths,
        and block tables — the pool a session lives in."""
        cache = cache_lib.init_paged_cache(
            self.cfg, first.shape[0], self.max_len, self.block_size,
            self.num_blocks, jnp.float32)
        cache["first"] = first.astype(jnp.int32)
        cache["length"] = lengths.astype(jnp.int32)
        cache["block_tables"] = tables.astype(jnp.int32)
        return cache

    def _paged_prefill_chunk_impl(self, params, toks, cache, l_end):
        return self._chunk_step(params, toks, cache, l_end=l_end)

    def _paged_zero_row_state(self):
        """Zeroed single-row non-pooled state (rolling/recurrent slots,
        enc K/V): the ``row_state`` a plain (non-fork) paged refill
        starts from.  Built once and reused — never donated."""
        if self._zero_state is None:
            full = self.model.init_cache(1, self.max_len, jnp.float32)
            st = {"slots": {n: full["slots"][n] for n in self._nonpooled}}
            if "enc" in full:
                st["enc"] = full["enc"]
            self._zero_state = st
        return self._zero_state

    def _paged_row_staging(self, cache, row_state, table_row, length0,
                           first0):
        """The 1-row staging cache of a paged admission: pooled slots
        alias the live pool (chunk scatter-writes land directly in the
        row's blocks via ``table_row``), non-pooled per-row slots come
        from ``row_state`` (zeros, or a prefix snapshot)."""
        slots = {}
        for name, _ in self.model.slots:
            if name in self._pooled_set:
                slots[name] = cache["slots"][name]
            else:
                slots[name] = row_state["slots"][name]
        stg = {"length": jnp.reshape(length0, (1,)).astype(jnp.int32),
               "first": jnp.reshape(first0, (1,)).astype(jnp.int32),
               "block_tables": table_row[None].astype(jnp.int32),
               "slots": slots}
        if "enc" in cache:
            stg["enc"] = row_state["enc"]
        return stg

    def staging_passes(self, k: int) -> Tuple[int, int, int]:
        """Pass layout of a staging prefill of ``k`` chunks, in chunks:
        ``(n, g, r)`` = ``n`` full passes of ``g`` chunks, then one pass
        of ``r`` chunks when ``r > 0``.  Static per ``k``, so each chunk
        count still compiles one program, and no pass is padded past the
        prompt's own chunks."""
        g = min(self._pass_chunks, k)
        n, r = divmod(k, g)
        return n, g, r

    def _staging_prefill(self, params, toks, staging, l_end=None):
        """Prefill ``toks`` [1, k*C] through the staging row in the
        passes of ``staging_passes(k)``; returns (the last pass's logits
        [1, V] f32, staging)."""
        n, g, r = self.staging_passes(toks.shape[1] // self.prefill_chunk)
        P = g * self.prefill_chunk

        def one(stg, t):
            logits, stg = self._chunk_step(params, t, stg, l_end=l_end)
            return logits.astype(jnp.float32), stg

        if n == 1:
            logits, staging = one(staging, toks[:, :P])
        else:
            def body(carry, j):
                t = jax.lax.dynamic_slice_in_dim(toks, j * P, P, axis=1)
                return one(carry[1], t), None

            logits0 = jnp.zeros((1, self.cfg.vocab_size), jnp.float32)
            (logits, staging), _ = jax.lax.scan(
                body, (logits0, staging), jnp.arange(n))
        if r:
            logits, staging = one(staging, toks[:, n * P:])
        return logits, staging

    def _paged_merge_staging(self, cache, staging, slot, l_end, first0,
                             table_row):
        """Fold a finished staging row back into the live cache: adopt
        the pool (the scatter-writes already landed there), swap the
        non-pooled per-row state into ``slot``, and point the slot's
        table/length/first at the new request."""
        new_slots = dict(cache["slots"])
        for name in self._pooled:
            new_slots[name] = staging["slots"][name]
        cache = dict(cache, slots=new_slots)
        dst = {"slots": {n: cache["slots"][n] for n in self._nonpooled},
               "first": cache["first"]}
        src = {"slots": {n: staging["slots"][n] for n in self._nonpooled},
               "first": jnp.reshape(first0, (1,)).astype(jnp.int32)}
        if "enc" in cache:
            dst["enc"] = cache["enc"]
            src["enc"] = staging["enc"]
        dst = cache_lib.insert_row(dst, src, jnp.int32(0), slot)
        merged = dict(cache["slots"])
        merged.update(dst["slots"])
        cache = dict(cache, slots=merged, first=dst["first"])
        if "enc" in dst:
            cache["enc"] = dst["enc"]
        l1 = jnp.reshape(l_end, (1,)).astype(jnp.int32)
        return dict(
            cache,
            length=jax.lax.dynamic_update_slice(cache["length"], l1,
                                                (slot,)),
            block_tables=jax.lax.dynamic_update_slice(
                cache["block_tables"], table_row[None].astype(jnp.int32),
                (slot, jnp.int32(0))))

    def _paged_refill_impl(self, params, toks, tok, cache, done, remaining,
                           idx, slot, budget, key, table_row, row_state,
                           length0, l_end, first0, gp: GenerationParams):
        """Fused paged admission — ONE dispatch for both flavors:

        * plain: ``toks`` [1, padded] left-padded, ``length0 = 0``,
          ``first0 = padded - p``, ``row_state`` zeros;
        * prefix fork: ``toks`` [1, ceil(q/C)*C] right-padded question
          suffix, ``length0 = L0`` (the cached prefix end), ``first0``
          the prefix's pad offset, ``row_state`` the prefix snapshot;
          ``table_row`` already shares the prefix's pool blocks.

        Prefills into the staging row, samples the first token,
        merges into ``slot`` and flips the decode carry live.  Only
        traced scalars differ between flavors, so both compile once per
        chunk count."""
        staging = self._paged_row_staging(cache, row_state, table_row,
                                          length0, first0)
        logits, staging = self._staging_prefill(params, toks, staging,
                                                l_end)
        tok_new = sample_token(logits, gp, key, 0)
        cache = self._paged_merge_staging(cache, staging, slot, l_end,
                                          first0, table_row)
        tok = jax.lax.dynamic_update_slice(tok, tok_new, (slot, 0))
        done = jax.lax.dynamic_update_slice(
            done, jnp.zeros((1,), done.dtype), (slot,))
        remaining = jax.lax.dynamic_update_slice(
            remaining, budget[None].astype(remaining.dtype), (slot,))
        idx = jax.lax.dynamic_update_slice(
            idx, jnp.zeros((1,), idx.dtype), (slot,))
        return tok, cache, done, remaining, idx

    def _paged_prefix_prefill_impl(self, params, toks, cache, table_row,
                                   l_end, first0, row_state):
        """Prefill a canonical retrieved-context prefix into its own
        block run (no live row touched).  Returns the cache (the pool
        now holds the prefix K/V) and the single-row snapshot of the
        non-pooled state at the prefix end — everything a later fork
        needs to resume from position ``l_end``."""
        staging = self._paged_row_staging(cache, row_state, table_row,
                                          jnp.int32(0), first0)
        _, staging = self._staging_prefill(params, toks, staging, l_end)
        new_slots = dict(cache["slots"])
        for name in self._pooled:
            new_slots[name] = staging["slots"][name]
        cache = dict(cache, slots=new_slots)
        snap = {"slots": {n: staging["slots"][n] for n in self._nonpooled}}
        if "enc" in staging:
            snap["enc"] = staging["enc"]
        return cache, snap

    def _paged_copy_block_impl(self, cache, src, dst):
        """Copy pool block ``src`` into ``dst`` for every pooled slot
        (all cycles at once) — the copy-on-write step when a fork's
        prefix ends mid-block."""
        slots = dict(cache["slots"])
        for name in self._pooled:
            kv = slots[name]

            def cp(buf):
                blk = jax.lax.dynamic_slice_in_dim(buf, src, 1, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(buf, blk, dst,
                                                           axis=1)

            slots[name] = {"k": cp(kv["k"]), "v": cp(kv["v"])}
        return dict(cache, slots=slots)

    def _cont_nb_cap(self, high: int) -> int:
        """Static block-table width for a paged decode segment: enough
        blocks to cover the highest position the segment can reach,
        rounded up to 4 blocks so distinct compiles stay bounded at
        nb_total/4 per GenerationParams.  This is the paged analogue of
        ``_cont_kv_cap`` — the decode read gathers ``nb_cap`` blocks, so
        per-step cost tracks live tokens instead of ``max_len``."""
        bs = self.block_size
        nb = -(-min(high, self.nb_total * bs) // bs)
        nb = -(-nb // 4) * 4
        return max(1, min(self.nb_total, nb))

    def _decode_cont_impl(self, params, tok, cache, key, done, remaining,
                          idx, out, t0, drain, gp: GenerationParams,
                          kv_cap=None, nb_cap=None):
        """Continuous decode segment: like ``_decode_loop_impl`` but
        with per-row ``remaining`` budgets and per-row output cursors
        ``idx``, exiting as soon as any row that was live at entry
        finishes (budget exhausted / EOS) so the host can swap the freed
        slot's cache state for the next request.  ``drain`` (traced
        bool) disables the per-completion exit — used when nothing is
        pending, so the frame finishes in one dispatch.  Rows decode at
        per-row relative positions (``Model.decode_step(relative=True)``).
        Returns (tok, done, remaining, idx, out, cache, summary) where
        ``summary`` packs [done, idx, t, length] into one int32 array —
        the only device->host transfer a segment needs."""
        max_new = gp.max_new_tokens
        done0 = done
        state = (jnp.asarray(t0, jnp.int32), tok, cache, done, remaining,
                 idx, out)

        def cond(st):
            _, _, _, done, _, _, _ = st
            return ~jnp.all(done) & (drain | ~jnp.any(done & ~done0))

        def body(st):
            t, tok, cache, done, remaining, idx, out = st
            active = ~done
            col = jnp.where(active, tok[:, 0], 0)
            hit = active[:, None] & (jnp.arange(max_new)[None, :]
                                     == idx[:, None])
            out = jnp.where(hit, col[:, None], out)
            idx = idx + active.astype(jnp.int32)
            remaining = remaining - active.astype(jnp.int32)
            done = done | (remaining <= 0)
            if gp.eos_id is not None:
                done = done | (active & (tok[:, 0] == gp.eos_id))

            def step(args):
                tok, cache = args
                if self.paged:
                    # finished rows must not touch the pool: their table
                    # entries may point at blocks already freed and
                    # re-allocated to live rows
                    logits, cache = self.model.decode_step(
                        params, tok, cache, relative=True, nb_cap=nb_cap,
                        active=~done)
                else:
                    logits, cache = self.model.decode_step(
                        params, tok, cache, kv_cap=kv_cap, relative=True)
                return sample_token(logits, gp, key, t + 1), cache

            # survivors must leave the segment holding an un-recorded
            # token, so the step also runs on the iteration that ends
            # the segment; it is skipped only when nothing is live
            tok, cache = jax.lax.cond(~jnp.all(done), step,
                                      lambda args: args, (tok, cache))
            return (t + 1, tok, cache, done, remaining, idx, out)

        t, tok, cache, done, remaining, idx, out = jax.lax.while_loop(
            cond, body, state)
        if self.paged:
            # per-row lengths: [done, idx, lengths, t] -> 3B + 1 ints
            summary = jnp.concatenate(
                [done.astype(jnp.int32), idx, cache["length"], t[None]])
        else:
            summary = jnp.concatenate(
                [done.astype(jnp.int32), idx,
                 jnp.stack([t, cache["length"]])])
        return tok, done, remaining, idx, out, cache, summary

    def cont_max_prompt_len(self, max_new_tokens: int) -> int:
        """Longest prompt a continuous session can serve: its chunk
        frames (``ceil(p/C)*C`` slots) plus the decode budget must fit
        the preallocated cache."""
        assert self.prefill_chunk is not None
        return max(0, self.max_len - max_new_tokens) \
            // self.prefill_chunk * self.prefill_chunk

    def _cont_kv_cap(self, high: int) -> Optional[int]:
        """Static decode-read cap for a continuous segment: the highest
        position the segment can reach, rounded up to 32 slots (the
        capped KV read is memcpy-bound, so a tight cap is the decode
        step's dominant cost knob; 32-granularity bounds distinct
        compiles at max_len/32 per GenerationParams)."""
        if self._exact_length:
            return None
        cap = -(-min(self.max_len, high) // 32) * 32
        return min(self.max_len, max(cap, _MIN_BUCKET))

    def continuous_session(self, gen: GenerationParams, key=None,
                           prefix_cache=None) -> "ContinuousSession":
        return ContinuousSession(self, gen, key=key,
                                 prefix_cache=prefix_cache)

    def _route_empty_prompts(self, prompts, gen: GenerationParams, key,
                             generate_fn) -> Optional[List[List[int]]]:
        """Empty prompts condition on nothing, so they get empty
        completions; the remaining rows run as a smaller wave.  Returns
        None when every prompt is non-empty (the common case).  Keeps an
        all-empty wave from ever reaching jit (on exact-length recurrent
        architectures it used to build a [B, 0] token batch and fail)."""
        keep = [i for i, p in enumerate(prompts) if len(p)]
        if len(keep) == len(prompts):
            return None
        outs: List[List[int]] = [[] for _ in prompts]
        if keep:
            sub = generate_fn([prompts[i] for i in keep], key=key, gen=gen)
            for i, o in zip(keep, sub):
                outs[i] = o
        return outs

    def _start(self, prompts, gen: GenerationParams, key):
        """Shared prompt-side setup: pad, prefill, sample token 0.
        Returns (token, cache, key, kv_cap) — ``kv_cap`` is the static
        bound on absolute positions this batch can reach (padded prompt
        length + decode budget), which caps the decode-side KV read."""
        if gen.max_new_tokens >= self.max_len:
            raise ValueError(
                f"max_new_tokens={gen.max_new_tokens} does not fit the "
                f"engine cache (max_len={self.max_len}); raise max_len or "
                f"lower max_new_tokens")
        prompts = self.clip_prompts(prompts, gen.max_new_tokens)
        bucket = self.prompt_bucket(max(len(p) for p in prompts),
                                    gen.max_new_tokens)
        toks, first = self._pad_batch(prompts, bucket)
        key = key if key is not None else jax.random.PRNGKey(0)
        tok, cache = self._prefill_sample(self.params, jnp.asarray(toks),
                                          jnp.asarray(first), key, gp=gen)
        # exact-length architectures keep KV (if any) in window-sized
        # buffers, so the cap buys nothing there while its per-prompt-
        # length static value would recompile the decode loop per length;
        # bucketed archs get one decode program per prompt bucket
        kv_cap = None if self._exact_length else \
            min(self.max_len, toks.shape[1] + gen.max_new_tokens)
        return tok, cache, key, kv_cap

    # ----------------------------------------------------------------- public

    def generate(self, prompts: List[List[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, key=None,
                 eos_id: Optional[int] = None,
                 gen: Optional[GenerationParams] = None
                 ) -> List[List[int]]:
        """Generate completions for up to ``batch_size`` prompts.

        Either pass a ``GenerationParams`` via ``gen`` or the legacy
        (max_new_tokens, temperature, eos_id) scalars.  Returns one
        token list per prompt (empty input -> empty output); EOS, when
        hit, is the last token of the row.
        """
        if gen is None:
            gen = GenerationParams(max_new_tokens=max_new_tokens,
                                   temperature=temperature, eos_id=eos_id)
        if not prompts or gen.max_new_tokens <= 0:
            return [[] for _ in prompts]
        empties = self._route_empty_prompts(prompts, gen, key, self.generate)
        if empties is not None:
            return empties
        tok, cache, key, kv_cap = self._start(prompts, gen, key)
        out, count, _ = self._decode_loop(self.params, tok, cache, key,
                                          jnp.int32(len(prompts)), gp=gen,
                                          kv_cap=kv_cap)
        out = np.asarray(out)                       # the one host transfer
        count = np.asarray(count)
        return [out[i, :count[i]].tolist() for i in range(len(prompts))]

    def generate_reference(self, prompts: List[List[int]],
                           max_new_tokens: int = 32,
                           temperature: float = 0.0, key=None,
                           eos_id: Optional[int] = None,
                           gen: Optional[GenerationParams] = None
                           ) -> List[List[int]]:
        """The original per-token Python loop (one host sync per token).
        Kept as the semantics reference for parity tests and as the
        baseline in benchmarks/serve_throughput.py."""
        if gen is None:
            gen = GenerationParams(max_new_tokens=max_new_tokens,
                                   temperature=temperature, eos_id=eos_id)
        if not prompts or gen.max_new_tokens <= 0:
            return [[] for _ in prompts]
        empties = self._route_empty_prompts(prompts, gen, key,
                                            self.generate_reference)
        if empties is not None:
            return empties
        tok, cache, key, kv_cap = self._start(prompts, gen, key)
        B = self.batch_size
        outs: List[List[int]] = [[] for _ in range(B)]
        done = [False] * B
        for t in range(gen.max_new_tokens):
            for i in range(len(prompts)):
                tid = int(tok[i, 0])                # per-token host sync
                if not done[i]:
                    outs[i].append(tid)
                    if gen.eos_id is not None and tid == gen.eos_id:
                        done[i] = True
            if all(done[:len(prompts)]):
                break
            logits, cache = self._decode(self.params, tok, cache,
                                         kv_cap=kv_cap)
            tok = sample_token(logits, gen, key, t + 1)
        return outs[:len(prompts)]


class ContinuousSession:
    """Host-side state machine for continuous batching on one engine.

    A session serves a stream of requests through *frames*: a frame
    starts by chunk-prefilling up to ``batch_size`` prompts together
    (left-padded to a shared multiple of ``prefill_chunk``), then runs
    compiled decode segments that return to the host whenever a row
    finishes.  The host swaps the freed slot's cache state for the next
    pending request — chunk-prefilled into a single-row staging cache
    whose frames end exactly at the shared absolute position, then
    ``insert_row``-ed into the live cache — and resumes the loop.  When
    the frame's positions near ``max_len`` (or nothing pending fits),
    finished slots idle until the frame drains and a fresh frame starts.

    All positions handed to the model are per-row relative, so a
    request's numerics match a solo run regardless of the admission
    offset; slots/buffers stay keyed by the shared absolute position.
    Scheduling policy (which request enters which slot) lives in
    ``serving.scheduler.ContinuousQueue``; this class only enforces
    geometry (``can_refill``) and runs the device programs.
    """

    def __init__(self, engine: ServeEngine, gen: GenerationParams, *,
                 key=None, prefix_cache=None):
        if engine.prefill_chunk is None:
            raise ValueError("engine was built without prefill_chunk=..., "
                             "which continuous batching requires")
        if gen.max_new_tokens < 1:
            raise ValueError("continuous batching needs max_new_tokens >= 1")
        if engine.cont_max_prompt_len(gen.max_new_tokens) < 1:
            raise ValueError(
                f"prefill_chunk={engine.prefill_chunk} + "
                f"max_new_tokens={gen.max_new_tokens} do not fit the "
                f"engine cache (max_len={engine.max_len})")
        self.eng = engine
        self.gen = gen
        self.C = engine.prefill_chunk
        self.B = engine.batch_size
        self.key = key if key is not None else jax.random.PRNGKey(0)
        # device-resident decode carry (rebound after every dispatch —
        # the compiled programs consume their donated inputs)
        self.cache = None
        self.tok = None                        # [B, 1]
        self.out = None                        # [B, max_new]
        self._done_d = None                    # [B] bool
        self._rem_d = None                     # [B] int32
        self._idx_d = None                     # [B] int32
        self._seg_key = None
        # host mirrors (updated from the segment summary / refill args)
        self.done = np.ones(self.B, bool)
        self.idx = np.zeros(self.B, np.int32)
        self._budget = np.zeros(self.B, np.int32)
        self.length = 0                        # mirrors cache["length"]
        self.tstep = 0
        self.admitted = 0
        self.frames = 0
        self.segments = 0
        self.refills = 0
        # staging prefills (refill, prefix prefill): model passes
        # dispatched and tokens staged, pads included
        self.prefill_passes = 0
        self.prefill_tokens = 0
        # slot -> request trace id (set by the scheduler at admission);
        # decode-segment spans and prefix-cache events attribute to it
        self.traces: Dict[int, Optional[str]] = {}
        # paged mode: host-side block bookkeeping.  ``lengths`` and
        # ``_first`` mirror the per-row cache["length"] and ["first"];
        # ``_tables`` mirrors the rows' block tables so freed rows can
        # return their blocks.
        self.paged = engine.paged
        self.prefix_cache = None
        if engine.paged:
            self.allocator = cache_lib.BlockAllocator(engine.num_blocks)
            self.lengths = np.zeros(self.B, np.int64)
            self._first = np.zeros(self.B, np.int64)
            self._tables = np.full((self.B, engine.nb_total), -1, np.int32)
            if prefix_cache is not None:
                from repro.serving.prefix_cache import PrefixCache
                if isinstance(prefix_cache, int):
                    prefix_cache = PrefixCache(capacity=prefix_cache)
                # an evicted entry returns its block refcounts; blocks
                # forked into live rows survive through the rows' refs
                prefix_cache.on_evict = \
                    lambda e: self.allocator.free(e.block_ids)
                self.prefix_cache = prefix_cache

    # ------------------------------------------------------------- geometry

    def _padded(self, prompt_len: int) -> int:
        return -(-max(1, prompt_len) // self.C) * self.C

    def free_slots(self) -> List[int]:
        return [i for i in range(self.B) if self.done[i]]

    def active(self) -> bool:
        return bool((~self.done).any())

    def can_refill(self, prompt_len: int, budget: int,
                   prefix_len: Optional[int] = None,
                   prompt: Optional[Sequence[int]] = None) -> bool:
        """A request fits mid-frame iff its padded chunk frames fit
        *below* the current shared position (its tokens occupy
        [length - p, length)) and its decode budget fits above.

        Paged sessions have no shared position: a request fits iff the
        allocator can hand out its block run (LRU prefix entries are
        evicted to make room), so admission continues indefinitely."""
        if not self.paged:
            return (self.cache is not None
                    and self._padded(prompt_len) <= self.length
                    and self.length + budget <= self.eng.max_len)
        if self.cache is None:
            return False
        prefix = self._prefix_parts(prompt, prefix_len)
        while True:
            need = self._plan_blocks(prompt_len, budget, prefix)
            if need is None:
                return False
            if self.allocator.can_alloc(need):
                return True
            if self.prefix_cache is None or not self.prefix_cache.evict_lru():
                return False

    def _prefix_parts(self, prompt, prefix_len) -> Optional[tuple]:
        """The shareable context-prefix tokens of a request, or None
        when the request takes the plain (no-fork) path.  At least one
        token is always left on the question side so the refill has a
        real suffix to prefill and sample from."""
        if (not self.paged or self.prefix_cache is None or not prefix_len
                or prompt is None):
            return None
        prefix_len = min(int(prefix_len), len(prompt) - 1)
        if prefix_len <= 0:
            return None
        return tuple(prompt[:prefix_len])

    def _plan_blocks(self, prompt_len: int, budget: int,
                     prefix: Optional[tuple]) -> Optional[int]:
        """Pool blocks a paged refill would newly allocate, or None when
        the request's span can never fit one row (`> max_len`)."""
        bs = self.eng.block_size
        if prefix is None:
            span = self._padded(prompt_len) + budget
            if span > self.eng.max_len:
                return None
            return -(-span // bs)
        p = len(prefix)
        L0 = p + (-p) % self.C
        span = L0 + (prompt_len - p) + budget
        if span > self.eng.max_len:
            return None
        tot = -(-span // bs)
        fork_new = tot - L0 // bs       # COW tail + fresh decode blocks
        if self.prefix_cache.peek(prefix) is not None:
            return fork_new
        return -(-L0 // bs) + fork_new  # prefix prefill allocates too

    def frame_capacity(self, requests: Sequence[Tuple[int, int]]) -> int:
        """How many of the first ``requests`` [(prompt_len, budget)]
        fit one frame — the FIFO prefix the queue should admit with
        ``begin_frame``.  Non-paged frames are bounded by batch size
        only; paged frames also need a block run per row (the prefix
        cache is cleared at frame start, so its blocks count as free)."""
        n = min(len(requests), self.B)
        if not self.paged:
            return n
        bs = self.eng.block_size
        avail = self.allocator.available
        if self.prefix_cache is not None:
            avail += self.prefix_cache.held_blocks()
        fit = 0
        for k in range(1, n + 1):
            frame_len = self._padded(max(pl for pl, _ in requests[:k]))
            if frame_len + max(b for _, b in requests[:k]) > self.eng.max_len:
                break
            need = sum(-(-(frame_len + b) // bs) for _, b in requests[:k])
            if need > avail:
                break
            fit = k
        return fit

    def admission_cost(self, prompt_len: int, budget: int,
                       prefix_len: Optional[int] = None,
                       prompt: Optional[Sequence[int]] = None) -> int:
        """Prefill chunks admitting this request would dispatch — the
        shortest-prefill-first scheduling key.  A cached prefix skips
        its own chunks entirely (only the question suffix prefills)."""
        prefix = self._prefix_parts(prompt, prefix_len)
        if prefix is not None:
            p = len(prefix)
            L0 = p + (-p) % self.C
            q_chunks = -(-(prompt_len - p) // self.C)
            if self.prefix_cache.peek(prefix) is not None:
                return q_chunks
            return L0 // self.C + q_chunks
        return self._padded(prompt_len) // self.C

    def _release_slot(self, slot: int) -> None:
        """Return a row's pool blocks to the allocator (idempotent)."""
        if not self.paged:
            return
        ids = self._tables[slot][self._tables[slot] >= 0]
        if ids.size:
            self.allocator.free(ids.tolist())
        self._tables[slot] = -1

    def release(self) -> None:
        """Free every pool block held by rows and prefix entries; after
        this ``allocator.available == num_blocks`` (the leak check)."""
        self.traces.clear()
        if not self.paged:
            return
        for i in range(self.B):
            self._release_slot(i)
        if self.prefix_cache is not None:
            self.prefix_cache.clear()

    def pool_fragmentation(self) -> float:
        """Internal fragmentation of the live rows: the fraction of
        allocated pool capacity (blocks x block_size tokens) not yet
        holding live tokens.  0.0 for non-paged sessions."""
        if not self.paged:
            return 0.0
        nblk = int((self._tables >= 0).sum())
        if nblk == 0:
            return 0.0
        used = int(self.lengths[~self.done].sum())
        return max(0.0, 1.0 - used / (nblk * self.eng.block_size))

    # ------------------------------------------------------------ admission

    def _count_staged(self, n_tokens: int) -> int:
        """Count a staging prefill of ``n_tokens`` (a chunk multiple);
        returns the model passes it dispatches."""
        n, _, r = self.eng.staging_passes(n_tokens // self.C)
        passes = n + (r > 0)
        self.prefill_passes += passes
        self.prefill_tokens += n_tokens
        return passes

    def _chunked_prefill(self, cache, toks: np.ndarray):
        logits = None
        for j in range(toks.shape[1] // self.C):
            logits, cache = self.eng._prefill_chunk(
                self.eng.params,
                jnp.asarray(toks[:, j * self.C:(j + 1) * self.C]), cache)
        return logits, cache

    def begin_frame(self, prompts: Sequence[Sequence[int]],
                    budgets: Sequence[int]) -> None:
        """Drop the previous frame and admit up to ``batch_size``
        prompts at position 0 through the shared [B, C] chunk program."""
        assert prompts and len(prompts) <= self.B
        assert all(len(p) for p in prompts) and not self.active()
        frame_len = self._padded(max(len(p) for p in prompts))
        toks = np.full((self.B, frame_len), self.eng.pad_id, np.int32)
        first = np.full((self.B,), frame_len, np.int32)
        for i, p in enumerate(prompts):
            toks[i, frame_len - len(p):] = p
            first[i] = frame_len - len(p)
        if self.paged:
            # a fresh frame rebuilds the pool, invalidating any cached
            # prefix content (paged sessions normally never get here
            # twice: mid-stream admission goes through refill instead)
            if self.prefix_cache is not None:
                self.prefix_cache.clear()
            for i in range(self.B):
                self._release_slot(i)
            bs = self.eng.block_size
            tables = np.full((self.B, self.eng.nb_total), -1, np.int32)
            for i in range(len(prompts)):
                ids = self.allocator.alloc(-(-(frame_len + budgets[i]) // bs))
                tables[i, :len(ids)] = ids
            cache = self.eng._paged_fresh_cache(
                jnp.asarray(first), jnp.zeros(self.B, jnp.int32),
                jnp.asarray(tables))
            logits = None
            for j in range(frame_len // self.C):
                logits, cache = self.eng._paged_prefill_chunk(
                    self.eng.params,
                    jnp.asarray(toks[:, j * self.C:(j + 1) * self.C]),
                    cache, jnp.int32(frame_len))
            self.cache = cache
            self._tables = tables
            self.lengths = np.full(self.B, frame_len, np.int64)
            self._first = first.astype(np.int64)
        else:
            cache = self.eng._fresh_cache(jnp.asarray(first),
                                          jnp.zeros((), jnp.int32))
            logits, self.cache = self._chunked_prefill(cache, toks)
        self.tok = sample_token(logits, self.gen,
                                jax.random.fold_in(self.key, self.frames),
                                0)
        self.out = jnp.zeros((self.B, self.gen.max_new_tokens), jnp.int32)
        self.done = np.arange(self.B) >= len(prompts)
        self.idx = np.zeros(self.B, np.int32)
        remaining = np.zeros(self.B, np.int32)
        remaining[:len(prompts)] = budgets
        self._budget = remaining.copy()
        self._done_d = jnp.asarray(self.done)
        self._rem_d = jnp.asarray(remaining)
        self._idx_d = jnp.asarray(self.idx)
        self._seg_key = jax.random.fold_in(self.key, 500 + self.frames)
        self.length = frame_len
        self.tstep = 0
        self.admitted += len(prompts)
        self.frames += 1
        # sync: dispatch is async, but "the frame's first tokens exist"
        # is the semantic moment callers stamp TTFT at
        jax.block_until_ready(self.tok)

    def refill(self, slot: int, prompt: Sequence[int], budget: int,
               prefix_len: Optional[int] = None) -> None:
        """Swap ``prompt`` into finished slot ``slot`` mid-frame — one
        fused dispatch (``ServeEngine._refill``): staging chunk prefill
        ending at the current shared position, first-token sample, row
        insert, live carry update.  The slot resumes decoding with the
        next segment.

        Paged sessions allocate the row's block run here instead; when
        ``prefix_len`` marks a retrieved-context prefix, its prefilled
        blocks are forked from the ``PrefixCache`` (refcounted, COW on
        a mid-block tail) and only the question suffix prefills."""
        p = len(prompt)
        ok = self.can_refill(p, budget, prefix_len, prompt)
        assert self.done[slot] and ok, (slot, p, budget, self.length)
        self.admitted += 1
        if self.paged:
            self._release_slot(slot)
            prefix = self._prefix_parts(prompt, prefix_len)
            if prefix is not None:
                self._refill_fork(slot, prompt, budget, prefix)
            else:
                self._refill_plain(slot, prompt, budget)
        else:
            padded = self._padded(p)
            toks = np.full((1, padded), self.eng.pad_id, np.int32)
            toks[0, padded - p:] = list(prompt)
            self._count_staged(padded)
            (self.tok, self.cache, self._done_d, self._rem_d,
             self._idx_d) = self.eng._refill(
                self.eng.params, jnp.asarray(toks), self.tok, self.cache,
                self._done_d, self._rem_d, self._idx_d, jnp.int32(slot),
                jnp.int32(p), jnp.int32(budget),
                jax.random.fold_in(self.key, 1000 + self.admitted),
                gp=self.gen)
        self.done[slot] = False
        self.idx[slot] = 0
        self._budget[slot] = budget
        self.refills += 1
        # sync (async dispatch): the refilled row's first token exists
        # now — the TTFT stamp callers take must not lead the device
        jax.block_until_ready(self.tok)

    def _dispatch_paged_refill(self, toks, slot, budget, table_row,
                               row_state, length0, l_end, first0) -> None:
        self._count_staged(toks.shape[1])
        (self.tok, self.cache, self._done_d, self._rem_d,
         self._idx_d) = self.eng._paged_refill(
            self.eng.params, jnp.asarray(toks), self.tok, self.cache,
            self._done_d, self._rem_d, self._idx_d, jnp.int32(slot),
            jnp.int32(budget),
            jax.random.fold_in(self.key, 1000 + self.admitted),
            jnp.asarray(table_row), row_state, jnp.int32(length0),
            jnp.int32(l_end), jnp.int32(first0), gp=self.gen)
        self._tables[slot] = table_row
        self.lengths[slot] = l_end
        self._first[slot] = first0

    def _refill_plain(self, slot: int, prompt: Sequence[int],
                      budget: int) -> None:
        bs = self.eng.block_size
        p = len(prompt)
        padded = self._padded(p)
        ids = self.allocator.alloc(-(-(padded + budget) // bs))
        table_row = np.full(self.eng.nb_total, -1, np.int32)
        table_row[:len(ids)] = ids
        toks = np.full((1, padded), self.eng.pad_id, np.int32)
        toks[0, padded - p:] = list(prompt)
        self._dispatch_paged_refill(toks, slot, budget, table_row,
                                    self.eng._paged_zero_row_state(),
                                    0, padded, padded - p)

    def _refill_fork(self, slot: int, prompt: Sequence[int], budget: int,
                     prefix: tuple) -> None:
        bs = self.eng.block_size
        entry = self.prefix_cache.get(prefix)
        tr = obs_trace.get_tracer()
        if tr.enabled:
            tr.event("prefix_cache", self.traces.get(slot),
                     hit=entry is not None, prefix_len=len(prefix))
        if entry is None:
            entry = self._prefill_prefix(prefix)
            self.prefix_cache.put(prefix, entry)
        suffix = list(prompt[len(prefix):])
        q = len(suffix)
        L0 = entry.length
        tot = -(-(L0 + q + budget) // bs)
        nfull = L0 // bs
        row_ids = self.allocator.fork(entry.block_ids[:nfull])
        if len(entry.block_ids) > nfull:
            # the prefix ends mid-block: the fork gets a private copy of
            # the tail block so its suffix writes never touch the entry
            cow = self.allocator.alloc(1)
            self.cache = self.eng._paged_copy_block(
                self.cache, jnp.int32(entry.block_ids[nfull]),
                jnp.int32(cow[0]))
            row_ids += cow
        row_ids += self.allocator.alloc(tot - len(row_ids))
        table_row = np.full(self.eng.nb_total, -1, np.int32)
        table_row[:tot] = row_ids
        kq = -(-q // self.C)
        toks = np.full((1, kq * self.C), self.eng.pad_id, np.int32)
        toks[0, :q] = suffix
        self._dispatch_paged_refill(toks, slot, budget, table_row,
                                    entry.row_state, L0, L0 + q, entry.pad)

    def _prefill_prefix(self, prefix: tuple):
        """Prefill a canonical prefix run (left-padded to a chunk
        multiple so relative positions are admission-invariant) and
        snapshot the row state at its end."""
        from repro.serving.prefix_cache import PrefixEntry
        bs = self.eng.block_size
        p = len(prefix)
        pad0 = (-p) % self.C
        L0 = p + pad0
        ids = self.allocator.alloc(-(-L0 // bs))
        table_row = np.full(self.eng.nb_total, -1, np.int32)
        table_row[:len(ids)] = ids
        toks = np.full((1, L0), self.eng.pad_id, np.int32)
        toks[0, pad0:] = list(prefix)
        passes = self._count_staged(L0)
        with obs_trace.get_tracer().span("prefix_prefill", tokens=p,
                                         passes=passes):
            self.cache, snap = self.eng._paged_prefix_prefill(
                self.eng.params, jnp.asarray(toks), self.cache,
                jnp.asarray(table_row), jnp.int32(L0), jnp.int32(pad0),
                self.eng._paged_zero_row_state())
        return PrefixEntry(block_ids=list(ids), length=L0, pad=pad0,
                           row_state=snap)

    # ------------------------------------------------------------- decoding

    def run_segment(self, drain: bool = False) -> List[Tuple[int, List[int]]]:
        """Advance the compiled decode loop until some live row
        finishes; with ``drain=True`` (nothing pending) run the whole
        frame to completion instead.  Returns the newly finished
        [(slot, tokens)].  One dispatch + one packed-summary transfer
        (plus the output buffer when rows finished)."""
        assert self.active()
        B = self.B
        live = ~self.done
        rem = self._budget[live] - self.idx[live]
        if self.paged:
            cap = None
            nbc = self.eng._cont_nb_cap(
                int((self.lengths[live] + rem).max()) + 2)
        else:
            cap = self.eng._cont_kv_cap(self.length + int(rem.max()) + 2)
            nbc = None
        # batched multi-trace span: one wall-clock interval, one event
        # per live request.  Guarded on tr.enabled so the disabled path
        # makes zero clock reads (NULL_SPAN; see tests/test_obs.py)
        tr = obs_trace.get_tracer()
        sp = obs_trace.NULL_SPAN
        if tr.enabled:
            tstep0 = self.tstep
            attrs = {}
            if self.paged:
                # blocks the paged kernel reads at entry (a row's
                # columns first // bs .. length // bs) against the
                # B x nb_cap a kernel walking every column reads
                bs = self.eng.block_size
                attrs = {"kv_blocks_live": int(
                    (self.lengths[live] // bs - self._first[live] // bs
                     + 1).sum()), "kv_blocks_grid": B * nbc}
            tif = int(self.lengths[live].sum()) if self.paged \
                else int(live.sum()) * self.length
            sp = tr.span("decode_segment",
                         traces=[self.traces.get(int(i))
                                 for i in np.nonzero(live)[0]],
                         rows=int(live.sum()), tokens_in_flight=tif,
                         drain=bool(drain), **attrs)
        with sp:
            (self.tok, self._done_d, self._rem_d, self._idx_d, self.out,
             self.cache, summary) = self.eng._decode_cont(
                self.eng.params, self.tok, self.cache, self._seg_key,
                self._done_d, self._rem_d, self._idx_d, self.out,
                jnp.int32(self.tstep), jnp.asarray(drain), gp=self.gen,
                kv_cap=cap, nb_cap=nbc)
            s = np.asarray(summary)             # the one per-segment sync
            done_new = s[:B].astype(bool)
            idx_new = s[B:2 * B]
            if self.paged:
                self.lengths = s[2 * B:3 * B].astype(np.int64)
                self.tstep = int(s[3 * B])
                self.length = int(self.lengths.max())
            else:
                self.tstep = int(s[2 * B])
                self.length = int(s[2 * B + 1])
            newly = np.nonzero(done_new & ~self.done)[0]
            events = []
            if newly.size:
                out_h = np.asarray(self.out)    # [B, max_new], small
                events = [(int(i), out_h[i, :idx_new[i]].tolist())
                          for i in newly]
                if self.paged:
                    # a finished row's blocks go straight back to the
                    # pool; the frozen row never reads or writes them
                    # again (decode runs it with active=False)
                    for i in newly:
                        self._release_slot(int(i))
            self.done = done_new
            self.idx = idx_new.astype(np.int32)
            self.segments += 1
            if tr.enabled:
                sp.set(finished=len(events), tstep=self.tstep,
                       steps=self.tstep - tstep0)
        return events
