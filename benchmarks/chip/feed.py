"""The benchmark's training feed: seeded token batches, made here and handed
to the program, so that every run of a seed trains on the same rows and the
reference reads the very rows the program was fed.

The generator is a copy of the program's `SyntheticTokens` noisy-bigram
chain (next token = a fixed permutation of the current one, replaced by a
uniform draw with probability `noise`), kept here so that no later change
to the program can change the benchmark's inputs. Every (step, shard) gets
its own counter-based stream, so the rows of every step differ.
"""
from __future__ import annotations

import numpy as np


class NoisyBigramTokens:
    def __init__(self, vocab_size: int, seed: int, noise: float):
        self.vocab = vocab_size
        self.seed = seed
        self.noise = noise
        perm_rng = np.random.Generator(np.random.Philox(key=seed % (2 ** 64)))
        self.perm = perm_rng.permutation(vocab_size).astype(np.int32)

    def batch(self, global_step: int, shard: int, num_shards: int,
              batch_per_shard: int, seq_len: int):
        """The `DataLoader` source interface: -> {"tokens", "labels"}, each
        int32 [batch_per_shard, seq_len]."""
        key = (self.seed * 0x9E3779B97F4A7C15
               + (global_step + 1) * num_shards + shard) % (2 ** 64)
        rng = np.random.Generator(np.random.Philox(key=key))
        n = seq_len + 1
        toks = np.empty((batch_per_shard, n), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, batch_per_shard)
        noise_mask = rng.random((batch_per_shard, n)) < self.noise
        noise_toks = rng.integers(0, self.vocab, (batch_per_shard, n),
                                  dtype=np.int32)
        for t in range(1, n):
            nxt = self.perm[toks[:, t - 1]]
            toks[:, t] = np.where(noise_mask[:, t], noise_toks[:, t], nxt)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make_source(traffic: dict, vocab_size: int, seed: int):
    data = traffic["data"]
    if data["source"] != "noisy_bigram":
        raise ValueError(f"unknown data source {data['source']!r}")
    return NoisyBigramTokens(vocab_size, seed, data["noise"])
