"""Generator of closed-loop serving traffic: a constant backlog of
generation requests whose prompt and output lengths follow exponential
laws with the mean lengths of a published workload.

Parameters (``bench/traffic/<mix>.json``):

  backlog            requests kept outstanding (admitted or queued); the
                     harness tops the engine back up to it on every step
  prompt_mean,       mean prompt and output lengths in tokens, as the
  output_mean        mix's ``source`` gives them
  schedule_requests  length of the repeating schedule of (prompt, output)
                     lengths
  schedule_seed      seed of that schedule

A source that gives only a mean fixes no shape; the exponential is the
law of greatest entropy for a given mean, so it adds no parameter of its
own. Lengths are rounded and at least 1. Where a request would not fit
the KV arena, it is cut to fit: the prompt to ``max_seq - 1``, then the
output to what the prompt leaves.

The lengths are drawn once from ``schedule_seed``, so every run offers
the same work in the same order; ``--seed`` draws the prompts' token ids
(uniform over the vocabulary). Why: with a fixed-size KV arena, which
requests a window holds moves tokens per second by tens of percent from
one order to another, which would swamp any change a run could show.
"""

from __future__ import annotations

import numpy as np


def schedule(params: dict, max_seq: int) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of the repeating schedule."""
    rng = np.random.default_rng(params["schedule_seed"])
    n = int(params["schedule_requests"])

    def draw(mean):
        return np.maximum(np.round(rng.exponential(mean, n)), 1) \
            .astype(np.int64)

    prompts = np.minimum(draw(params["prompt_mean"]), max_seq - 1)
    outputs = np.minimum(draw(params["output_mean"]), max_seq - prompts)
    return prompts, outputs


class Backlog:
    """The stream of requests of one run: ``next()`` gives the i-th
    request's prompt token ids and its output length."""

    def __init__(self, params: dict, seed: int, vocab: int, max_seq: int):
        self.prompts, self.outputs = schedule(params, max_seq)
        self.size = int(params["backlog"])
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.issued = 0

    def next(self) -> tuple[np.ndarray, int]:
        i = self.issued % len(self.prompts)
        self.issued += 1
        ids = self.rng.integers(0, self.vocab, int(self.prompts[i]),
                                dtype=np.int32)
        return ids, int(self.outputs[i])
