"""Rows of a language-model training job, generated from ``--seed`` by
one general generator driven by the parameters in
``workloads/<cell>.json`` (``traffic.generator`` names a function of
this file as ``traffic_lm.<function>``). A later cell of the
``train_lm`` entry adds such a file, not code here.

As ``traffic.py``: the multiset of document lengths comes from the
cell file's ``base_seed``; ``--seed`` deals the documents out in an
order of its own and writes the tokens, so every seed offers the same
work.
"""

from __future__ import annotations

import numpy as np

import traffic


def packed_rows(spec: dict, seed: int, vocab_size: int):
    """``rows`` rows of ``seq_len`` positions, packed from documents:
    lengths from ``spec["length"]`` (``traffic.draw_lengths``), packed
    greedily in arrival order (a document that does not fit the rest
    of a row opens the next row; nothing is split), the tail of a row
    padded with id 0; token ids Zipf(``zipf_s``) over the non-pad ids
    1 .. ``vocab_size - 1`` (id = rank). Documents are not marked: a
    row is one stream. Returns ``(x, x)``: a language model's targets
    are its inputs."""
    n, l = spec["rows"], spec["seq_len"]
    # enough documents for every row at the shortest mean a clip allows
    pool = traffic.draw_lengths(
        spec["length"], spec["documents"], traffic._rng(spec["base_seed"], 2))
    pool = np.minimum(pool, l)
    order = traffic._rng(seed, 2).permutation(len(pool))
    filled = np.zeros(n, np.int64)
    row = 0
    for d in pool[order]:
        if filled[row] + d > l:
            row += 1
            if row == n:
                break
        filled[row] += d
    else:
        raise ValueError(
            f"{len(pool)} documents fill only {row + 1} of {n} rows: "
            "raise traffic.documents")
    rng = traffic._rng(seed, 3)
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(spec.get("zipf_s", 1.0)))
    cdf /= cdf[-1]
    ids = 1 + np.searchsorted(cdf, rng.random((n, l), dtype=np.float32))
    ids = np.minimum(ids, vocab_size - 1).astype(np.int32)
    x = np.where(np.arange(l)[None, :] < filled[:, None], ids, 0)
    return x.astype(np.int32), x.astype(np.int32)
