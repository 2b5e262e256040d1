"""The one traffic generator: a mix file's parameters -> requests.

A mix (``traffic/<mix>.json``) gives:

    slots               decode slots of the scheduler
    requests_per_call   requests handed to one ``serve_requests`` call
    prompt_lengths      the prompt lengths drawn from, uniformly
    new_tokens          output tokens of every request
    page_tokens         tokens per KV page
    pool_share          pool pages as a share of what the slots can hold
    check_requests      requests the output check compares

Every seed serves the same lengths in another order, so every run does
the same work: a call of at least as many requests as there are lengths
holds the lengths in turn (request j the j-th, cyclically), shuffled by
the seed; smaller calls take their lengths from consecutive blocks of one
of each, each block shuffled by the seed. Token ids are uniform over the
vocabulary and drawn per request from the seed, so no two prompts share a
page (the prefix cache finds nothing to share).
"""
from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.lengths = [int(n) for n in mix["prompt_lengths"]]
        self.slots = int(mix["slots"])
        self.per_call = int(mix["requests_per_call"])
        self.new_tokens = int(mix["new_tokens"])
        self.page_tokens = int(mix["page_tokens"])

    @property
    def capacity(self) -> int:
        """Tokens a slot must hold: the longest prompt and its output, in
        whole pages."""
        pages = -(-(max(self.lengths) + self.new_tokens) // self.page_tokens)
        return pages * self.page_tokens

    @property
    def pool_pages(self) -> int:
        per_slot = self.capacity // self.page_tokens
        share = float(self.mix.get("pool_share", 1.0))
        return int(round(self.slots * per_slot * share)) + 1   # + scratch

    def length(self, r: int) -> int:
        n, k = len(self.lengths), self.per_call
        if k >= n:                      # each call holds the lengths in turn
            perm = np.random.default_rng((self.seed, 0, r // k)).permutation(k)
            return self.lengths[int(perm[r % k]) % n]
        perm = np.random.default_rng((self.seed, 0, r // n)).permutation(n)
        return self.lengths[int(perm[r % n])]

    def prompt(self, r: int, length: int | None = None, stream: int = 1):
        n = self.length(r) if length is None else length
        rng = np.random.default_rng((self.seed, stream, r))
        return rng.integers(0, self.vocab, n, dtype=np.int32)

    def call(self, i: int) -> list[tuple[int, np.ndarray]]:
        """The (request id, prompt) pairs of window call ``i``."""
        first = i * self.per_call
        return [(r, self.prompt(r)) for r in range(first, first + self.per_call)]

    def warmup(self) -> list[np.ndarray]:
        """One call that fills every slot and visits every prompt length,
        so set-up compiles each program the window runs."""
        n = max(min(self.slots, self.per_call), len(self.lengths))
        return [self.prompt(j, self.lengths[j % len(self.lengths)], stream=2)
                for j in range(n)]
