"""Seeded low-rank bigram reward over token continuations.

log R(x) = beta * sum_t <U[x_t], V[x_{t+1}]> / sqrt(rank), summed over the
continuation's consecutive pairs and the pair it forms with the prompt's
last token (``boundary``).  U and V are (vocab, rank) tables of standard
normals drawn by ``numpy.random.RandomState(seed)``: U first, then V.  It
stands for a learned reward model at a cost that is small next to the
policy's.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..envs.base import EnvSpec, RewardModule


def bigram_tables(seed: int, vocab: int, rank: int):
    rs = np.random.RandomState(seed)
    u = rs.standard_normal((vocab, rank)).astype(np.float32)
    v = rs.standard_normal((vocab, rank)).astype(np.float32)
    return u, v


class BigramReward(RewardModule):
    def __init__(self, vocab: int, rank: int = 16, beta: float = 1.0,
                 seed: int = 0, boundary: int = 0):
        self.vocab, self.rank, self.beta = vocab, rank, beta
        self.seed, self.boundary = seed, boundary

    def init(self, key, env_spec: EnvSpec):
        u, v = bigram_tables(self.seed, self.vocab, self.rank)
        return {"u": jnp.asarray(u), "v": jnp.asarray(v),
                "beta": jnp.asarray(self.beta, jnp.float32)}

    def log_reward(self, terminal_repr, params):
        tokens = terminal_repr.tokens
        first = jnp.full(tokens.shape[:1] + (1,), self.boundary, tokens.dtype)
        seq = jnp.concatenate([first, tokens], axis=1)
        score = jnp.sum(params["u"][seq[:, :-1]] * params["v"][seq[:, 1:]],
                        axis=(1, 2))
        return params["beta"] * score / np.sqrt(self.rank)
