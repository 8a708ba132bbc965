"""Online query identifier (paper §IV-A): PPO policy + feedback buffer.

Maps query embeddings to node-relevance probability vectors s_i in Δ^N,
samples routing actions, accumulates (embedding, action, feedback)
triples in a memory buffer, and triggers a batched PPO update whenever
the buffer passes a threshold (decoupling updates from transient
fluctuations; paper: ~30 ms per 1000 queries, threshold set from the
long-horizon average query load).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ppo
from repro.obs import trace as obs_trace


class OnlineQueryIdentifier:
    def __init__(self, embed_dim: int, n_nodes: int, *, seed: int = 0,
                 update_threshold: int = 256, update_epochs: int = 4,
                 lr: float = 3e-4, clip_eps: float = 0.02,
                 entropy_beta: float = 0.01):
        key = jax.random.PRNGKey(seed)
        self.params = ppo.init_policy(key, embed_dim, n_nodes)
        self.old_params = jax.tree.map(lambda x: x, self.params)
        self.opt_state = ppo.init_adam(self.params)
        self.n_nodes = n_nodes
        self.update_threshold = update_threshold
        self.update_epochs = update_epochs
        self.lr, self.clip_eps, self.entropy_beta = lr, clip_eps, entropy_beta
        self._buf_e: List[np.ndarray] = []
        self._buf_a: List[np.ndarray] = []
        self._buf_f: List[np.ndarray] = []
        self.updates_done = 0
        self._rng = np.random.default_rng(seed)

    # -------------------------------------------------------------- routing

    def identify(self, embeddings: np.ndarray) -> np.ndarray:
        """[B, D] -> probability vectors S^t [B, N] (Σ_n s_in = 1)."""
        probs = ppo.act_probs(self.params, jnp.asarray(embeddings))
        return np.asarray(probs)

    def sample_actions(self, probs: np.ndarray) -> np.ndarray:
        cum = probs.cumsum(axis=1)
        r = self._rng.random((probs.shape[0], 1))
        return (r > cum).sum(axis=1).clip(0, self.n_nodes - 1)

    # ------------------------------------------------------------- feedback

    def feedback(self, embeddings: np.ndarray, actions: np.ndarray,
                 scores: np.ndarray) -> None:
        """Record composite quality feedback f_i (Eq. 9) for routed queries."""
        self._buf_e.append(np.asarray(embeddings, np.float32))
        self._buf_a.append(np.asarray(actions, np.int32))
        self._buf_f.append(np.asarray(scores, np.float32))

    def buffered(self) -> int:
        return int(sum(len(a) for a in self._buf_a))

    def maybe_update(self) -> Optional[dict]:
        if self.buffered() < self.update_threshold:
            return None
        with obs_trace.get_tracer().span("ppo_update",
                                         queries=self.buffered()):
            e = jnp.asarray(np.concatenate(self._buf_e))
            a = jnp.asarray(np.concatenate(self._buf_a))
            f = jnp.asarray(np.concatenate(self._buf_f))
            self._buf_e, self._buf_a, self._buf_f = [], [], []
            self.old_params = jax.tree.map(lambda x: x, self.params)
            metrics = {}
            for _ in range(self.update_epochs):   # batch reuse via CLIP
                self.params, self.opt_state, metrics = ppo.ppo_update(
                    self.params, self.old_params, self.opt_state, e, a, f,
                    eps=self.clip_eps, beta=self.entropy_beta, lr=self.lr)
            self.updates_done += 1
            return {k: float(v) for k, v in metrics.items()}
