"""The one traffic generator: reads a mix file and yields a request queue.

A mix (``bench/traffic/<name>.json``) fixes the serving geometry and the
length distributions.  Lengths are a fixed set: the quantiles
``(i + 0.5) / n`` of the mix's clipped lognormal, paired and ordered
once by the mix's own ``layout_seed``.  A pair that does not fit the
slot's context (``max_pages_per_slot * page_size`` positions) keeps its
answer and has its prompt cut to what is left, as a server cuts a prompt
to the context less the answer budget.  So every ``--seed`` serves the
same sizes in the same order, and the seed draws only the token ids
(and, elsewhere, the weights): a random order per seed would move the
work that fits in the window.

This copies the seeding idea of ``repro.serving.scheduler.poisson_workload``
(seeded ids, lengths from the mix) with heavy-tailed lengths in place of
uniform ones and with every request due at the start: an offline queue
long enough that the slots stay full for the whole window.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

__all__ = ["quantile_lengths", "context", "queue_sizes", "queue_tokens",
           "tokens_processed"]


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of the clipped lognormal
    ``spec`` = {dist: lognormal, median, sigma, min[, max]}."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = [spec["median"] * math.exp(spec["sigma"] * v) for v in z]
    return np.clip(np.round(raw), spec["min"],
                   spec.get("max", math.inf)).astype(np.int64)


def context(mix: dict) -> int:
    """Cache positions one slot holds: prompt plus answer."""
    return mix["max_pages_per_slot"] * mix["page_size"]


def queue_sizes(mix: dict) -> list[tuple[int, int]]:
    """The queue's (prompt_len, output_len) pairs in serving order."""
    n = mix["queue_requests"]
    prompts = quantile_lengths(mix["prompt_len"], n)
    outputs = quantile_lengths(mix["output_len"], n)
    rng = np.random.default_rng(mix["layout_seed"])
    outputs = outputs[rng.permutation(n)]
    prompts = np.minimum(prompts, context(mix) - outputs)
    if prompts.min() < 1:
        raise ValueError("an answer fills the whole context: lower "
                         "output_len.max")
    order = rng.permutation(n)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def queue_tokens(mix: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """The queue's prompts: token ids drawn from the seed."""
    rng = np.random.default_rng([seed, 0])
    return [rng.integers(0, vocab, size=(p,)).astype(np.int32)
            for p, _ in queue_sizes(mix)]


def tokens_processed(prompt_len: int, n_out: int) -> int:
    """Positions a request runs through the model: its prompt and every
    emitted token but the last, which is never fed back."""
    return prompt_len + max(n_out - 1, 0)
