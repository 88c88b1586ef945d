"""GFlowNet fine-tuning of a language-model policy: continue a fixed prompt.

The prompt (``prompt_len`` ids drawn by ``numpy.random.RandomState(seed)``
from ``[0, vocab)``) is part of the environment, not of its state: a state
is the continuation so far, and an action appends one token.  Every
trajectory appends ``length`` tokens (no stop action), so the backward
policy is degenerate (pop the last token), as in
:class:`AutoregressiveEnvironment`.  The reward is
:class:`repro.rewards.bigram.BigramReward` over the continuation and its
boundary with the prompt (Hu et al. 2023, "Amortizing intractable
inference in large language models", with a seeded reward model in place
of a learned one).

Policies read the prompt from ``env.prompt`` (``make_lm_policy``).  A
backward step removes the newest token, but a cache built from a terminal
sequence cannot answer the states before it (a query needs the hidden
state of the state's last token), so ``incremental_pop_only`` is off and
backward rollouts re-encode.
"""
from __future__ import annotations

import numpy as np

from ..rewards.bigram import BigramReward
from .sequences import AutoregressiveEnvironment


def make_prompt(seed: int, vocab: int, prompt_len: int) -> np.ndarray:
    return np.random.RandomState(seed).randint(
        0, vocab, size=prompt_len).astype(np.int32)


class LMTokenEnvironment(AutoregressiveEnvironment):
    incremental_pop_only = False

    def __init__(self, vocab: int = 20480, length: int = 64,
                 prompt_len: int = 256, rank: int = 16, beta: float = 1.0,
                 seed: int = 0):
        self.prompt = make_prompt(seed, vocab, prompt_len)
        super().__init__(BigramReward(vocab, rank, beta, seed=seed + 1,
                                      boundary=int(self.prompt[-1])),
                         length=length, vocab=vocab)
