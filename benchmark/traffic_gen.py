"""The one traffic generator: a mix is a data file, this reads it.

Serving mixes repeat: the file gives two length ranges and a table length
K.  K prompt lengths and K output lengths are laid evenly across their
ranges and paired by the permutation written in the file, so every seed
issues the SAME multiset of (prompt, output) lengths every K requests.
A cycle is issued in the file's ``issue_order``, the same for every seed
(a window shorter than a few cycles holds the ramp and part of the first
cycle, so the order decides how much work falls inside it: a seeded order
moved the decode cell 3% between seeds, PERF.md); the seed draws the token
ids.  Training mixes fix batch and sequence length; the seed picks the
corpus.  The program sees nothing but requests or rows.

The request construction (ids from [1, vocab)) follows the package's
``serve/load.py::make_load``; the length draw is replaced by the table.
"""

from __future__ import annotations

import numpy as np


def serve_table(mix: dict) -> list[tuple[int, int]]:
    """The K (prompt length, output length) pairs of one cycle, table
    order."""
    k = int(mix["table_len"])
    pairing = list(mix["pairing"])
    if sorted(pairing) != list(range(k)):
        raise ValueError(f"{mix['name']}: pairing is not a permutation "
                         f"of 0..{k - 1}")
    prompts = np.rint(np.linspace(*mix["prompt_len"], k)).astype(int)
    outputs = np.rint(np.linspace(*mix["output_len"], k)).astype(int)
    return [(int(prompts[i]), int(outputs[pairing[i]])) for i in range(k)]


def serve_requests(mix: dict, seed: int, vocab: int, max_len: int,
                   cycles: int | None = None):
    """[(uid, prompt ids, max new tokens)] for `cycles` cycles of the
    table (default: the mix's ``queue_cycles``), all queued at once."""
    table = serve_table(mix)
    for p, o in table:
        if p + o > max_len:
            raise ValueError(f"{mix['name']}: prompt {p} + output {o} "
                             f"exceeds the context {max_len}")
    order = list(mix["issue_order"])
    if sorted(order) != list(range(len(table))):
        raise ValueError(f"{mix['name']}: issue_order is not a permutation")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(cycles if cycles is not None else mix["queue_cycles"]):
        for i in order:
            plen, new = table[i]
            prompt = rng.integers(1, vocab, size=plen, dtype=np.int64)
            out.append((len(out), prompt, int(new)))
    return out


def chunk_counts(mix: dict, chunk: int) -> dict:
    """What one cycle of the table costs in prefill chunks and tokens."""
    table = serve_table(mix)
    return {"requests": len(table),
            "prompt_tokens": sum(p for p, _ in table),
            "output_tokens": sum(o for _, o in table),
            "chunks": sum(-(-p // chunk) for p, _ in table)}


def markov_corpus(seed: int, rows: int, row_len: int, vocab: int
                  ) -> np.ndarray:
    """Rows of a seeded first-order Markov chain over 512 ids drawn from
    the whole vocabulary (id 0, the package's pad id, is left out; the
    top id is forced in, because the package sizes its vocabulary as the
    largest id + 1).  Each state moves to one of four successors with
    odds 70/15/10/5, so there is structure to learn.  Copied from
    ``chip_smoke.py::write_corpus``."""
    rng = np.random.default_rng(seed)
    active = rng.choice(np.arange(1, vocab - 1), size=min(512, vocab - 2),
                        replace=False)
    active[0] = vocab - 1
    successors = rng.integers(0, len(active), (len(active), 4))
    state = rng.integers(0, len(active), rows)
    state[0] = 0
    tokens = np.empty((rows, row_len), np.int32)
    for t in range(row_len):
        tokens[:, t] = active[state]
        state = successors[state, rng.choice(4, size=rows,
                                             p=[0.7, 0.15, 0.1, 0.05])]
    return tokens
