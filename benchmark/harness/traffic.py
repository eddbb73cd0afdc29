"""The one general generator of training traffic. A traffic mix is a data
file of parameters (``benchmark/workloads/<cell>.json``); everything is
drawn from ``--seed`` on the host with numpy, every seed gets the same
shapes and the same amount of work.

Parameters: ``seq``, ``rows_per_chip``, ``feed`` (``"pool"``: batches made
in set-up and cycled in a seeded order; ``"loader"``: a seeded corpus
read by the program's own loader), ``pool_batches`` or ``corpus_rows``,
``prefetch``, and optionally ``mlm`` (masked-LM fields: ``max_predictions``,
``min_predictions``, ``mask_id``, ``first_free_id``, ``padded_rows_share``,
``padded_tail_share``).
"""

from __future__ import annotations

import numpy as np

INT31 = 2 ** 31 - 1


def rng_for(seed: int, *stream: int):
    return np.random.default_rng([int(seed), *stream])


def corpus(seed: int, rows: int, seq: int, vocab: int, first_id: int = 0):
    return rng_for(seed, 1).integers(first_id, vocab, (rows, seq),
                                     dtype=np.int32)


def mlm_batch(seed: int, index: int, rows: int, seq: int, vocab: int,
              mlm: dict) -> dict:
    """One batch of masked-LM rows in the gathered-positions format."""
    rng = rng_for(seed, 2, index)
    free = mlm["first_free_id"]
    tokens = rng.integers(free, vocab, (rows, seq), dtype=np.int32)
    tokens[:, 0] = 1                          # a [CLS] slot, never masked
    attn = np.ones((rows, seq), np.int32)
    padded = int(rows * mlm["padded_rows_share"])
    tail = int(seq * mlm["padded_tail_share"])
    if padded and tail:
        attn[rows - padded:, seq - tail:] = 0
    n_max = mlm["max_predictions"]
    ids = tokens.copy()
    positions = np.zeros((rows, n_max), np.int32)
    labels = np.zeros((rows, n_max), np.int32)
    weights = np.zeros((rows, n_max), np.float32)
    counts = rng.integers(mlm["min_predictions"], n_max + 1, rows)
    for r in range(rows):
        valid = int(attn[r].sum())
        n = int(min(counts[r], valid - 1))
        chosen = np.sort(rng.choice(np.arange(1, valid), n, replace=False))
        positions[r, :n], labels[r, :n] = chosen, tokens[r, chosen]
        weights[r, :n] = 1.0
        kind = rng.random(n)
        ids[r, chosen[kind < 0.8]] = mlm["mask_id"]
        swap = chosen[(kind >= 0.8) & (kind < 0.9)]
        ids[r, swap] = rng.integers(free, vocab, len(swap))
    return {"ids": ids, "types": np.zeros((rows, seq), np.int32),
            "attn": attn, "positions": positions, "mlm_labels": labels,
            "mlm_weights": weights,
            "nsp_labels": rng.integers(0, 2, rows).astype(np.int32)}


class Traffic:
    """``batch(t)`` is the traffic batch of step ``t`` (global rows, with
    one dropout seed per chip)."""

    def __init__(self, params: dict, vocab: int, seed: int, chips: int,
                 feed=None):
        self.params, self.chips, self.seed = params, chips, int(seed)
        self.rows = params["rows_per_chip"] * chips
        self.seq = params["seq"]
        self.tokens_per_step = self.rows * self.seq
        self._seed_base = int(rng_for(seed, 3).integers(1, 2 ** 30))
        self._loader = self.corpus = None
        if params["feed"] == "pool":
            n = params["pool_batches"]
            self._pool = [mlm_batch(seed, i, self.rows, self.seq, vocab,
                                    params["mlm"]) for i in range(n)]
            self._order = rng_for(seed, 4).permutation(n)
        elif params["feed"] == "loader":
            self.corpus = corpus(seed, params["corpus_rows"], self.seq, vocab)
            self._loader = iter(feed(self.corpus, self.rows,
                                     self.seed % INT31, params["prefetch"]))
            self.max_steps = params["corpus_rows"] // self.rows
        else:
            raise ValueError(f"unknown feed {params['feed']!r}")

    def dropout_seeds(self, t: int):
        return [(self._seed_base + t * self.chips + s) % INT31
                for s in range(self.chips)]

    def batch(self, t: int) -> dict:
        if self._loader is None:
            out = dict(self._pool[self._order[t % len(self._order)]])
        else:
            if t >= self.max_steps:
                raise RuntimeError(
                    f"the window asked for step {t} of a corpus that holds "
                    f"{self.max_steps} batches: raise corpus_rows")
            out = {"ids": next(self._loader)}
        out["seed"] = self.dropout_seeds(t)
        return out

    def rows_of_corpus(self, ids) -> np.ndarray:
        """The corpus rows a loader's batch holds, by index; raises where
        a row is not the corpus's (the loader altered it)."""
        index = {row.tobytes(): i for i, row in enumerate(self.corpus)}
        try:
            return np.asarray([index[np.ascontiguousarray(r).tobytes()]
                               for r in ids])
        except KeyError:
            raise ValueError("the loader yielded a row that is not in the "
                             "corpus") from None

    def close(self):
        close = getattr(self._loader, "close", None)
        if close is not None:
            close()
