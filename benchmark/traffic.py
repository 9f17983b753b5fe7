"""The one traffic generator: every mix is a data file it reads.

A serving mix (``benchmark/traffic/<name>.json``) states a loop kind, a
client count, groups of prompt lengths and a range of output lengths. From
those alone -- not from the seed -- it builds a fixed *population* of
(prompt length, output length) pairs in a fixed order, so that every seed
offers the same sizes in the same order; ``--seed`` makes every token (and
the weights). With the order drawn from the seed too, a 51 s window of the
decode mix, which holds some 80 requests of 48-144 tokens, gave tokens/s
that spread by 4.9 % from seed to seed while two runs of one seed agreed to
0.1 % (chip runs, PR 24): the seed was changing the work. A training mix
states how many token batches are cycled.
"""

from __future__ import annotations

import numpy as np


def lengths(lo: int, hi: int, count: int) -> list:
    """``count`` whole lengths spread evenly over ``[lo, hi]``."""
    return sorted({int(round(x)) for x in np.linspace(lo, hi, count)})


def prompt_lengths(mix: dict) -> list:
    """Every prompt length the mix can send (set-up warms each of them)."""
    out = set()
    for g in mix["prompt_groups"]:
        out.update(lengths(g["lo"], g["hi"], g["lengths"]))
    return sorted(out)


def padded_lengths(mix: dict, page_tokens: int) -> list:
    """Prompt lengths rounded up to whole pages: one compiled prefill each."""
    return sorted({-(-p // page_tokens) * page_tokens
                   for p in prompt_lengths(mix)})


def population(mix: dict) -> list:
    """The fixed multiset of (prompt length, output length) pairs, drawn
    once from the mix's own ``population_seed``: the same for every run."""
    rng = np.random.default_rng(mix["population_seed"])
    shares = np.asarray([g["share"] for g in mix["prompt_groups"]], float)
    group_lengths = [lengths(g["lo"], g["hi"], g["lengths"])
                     for g in mix["prompt_groups"]]
    outs = lengths(mix["output"]["lo"], mix["output"]["hi"],
                   mix["output"]["lengths"])
    n = mix["population"]
    # Equal counts of every group (by share), every length and every output
    # length, paired by a fixed shuffle.
    groups = np.repeat(np.arange(len(shares)),
                       np.round(shares / shares.sum() * n).astype(int))[:n]
    pairs = []
    out_cycle = rng.permutation(np.resize(outs, len(groups)))
    for g in range(len(shares)):
        idx = np.flatnonzero(groups == g)
        plen = rng.permutation(np.resize(group_lengths[g], len(idx)))
        pairs += [(int(p), int(out_cycle[i])) for p, i in zip(plen, idx)]
    return [pairs[i] for i in rng.permutation(len(pairs))]


class ServeTraffic:
    """Requests of one run: the population in its fixed order, the seed's
    tokens, and each client's first output length scaled (by the mix's own
    draw, the same in every run) so that the clients do not finish
    together."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.clients = int(mix["clients"])
        self._pairs = population(mix)
        longest = max(p for p, _ in self._pairs)
        self._tokens = np.random.default_rng(seed).integers(
            0, vocab, (len(self._pairs), longest), dtype=np.int32)
        fixed = np.random.default_rng(mix["population_seed"] + 1)
        lo, hi = mix["first_output_scale"]
        self._first_scale = fixed.uniform(lo, hi, self.clients)
        # The clients' first requests between them use every prompt length
        # of the mix (as far as there are clients), so that filling the
        # lanes also warms every shape.
        every = fixed.permutation(prompt_lengths(mix))
        self.first_lengths = [int(every[c % len(every)])
                              for c in range(self.clients)]
        self._next = 0

    def next_request(self, client: int, first: bool):
        """(id, prompt tokens, max new tokens) of the next request."""
        i = self._next % len(self._pairs)
        self._next += 1
        plen, out = self._pairs[i]
        if first:
            plen = self.first_lengths[client]
            out = max(2, int(round(out * self._first_scale[client])))
        return (f"c{client}-r{self._next}",
                [int(t) for t in self._tokens[i, :plen]], out)

    def warmup_prompt(self, length: int):
        """A prompt of a given length for set-up (tokens of row 0)."""
        row = self._tokens[0]
        return [int(t) for t in np.resize(row, length)]
