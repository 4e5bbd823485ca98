"""The one traffic generator: a mix is a data file, this reads it.

A mix file (``traffic/<mix>.json``) names its ``driver`` and gives that
driver's parameters.  The seed's freedom is put where it does not change the
work: token ids, masked positions and the order of requests — never the
multiset of lengths, the batch shape or the number of masked positions,
which are the same in every run of every seed.
"""
import numpy as np


def _rng(seed, *stream):
    return np.random.default_rng([int(seed), *map(int, stream)])


def mlm_batches(mix, vocab_size, seed):
    """``mix["pool"]`` seeded MLM batches, every row different: sequences
    packed to the full length (attention_mask all ones, fed so the key-mask
    attention path is the one timed), a segment split per row, and exactly
    ``round(mask_frac·seq_len)`` masked positions per row."""
    b, s = int(mix["batch"]), int(mix["seq_len"])
    n_mask = round(float(mix["mask_frac"]) * s)
    out = []
    for i in range(int(mix["pool"])):
        rng = _rng(seed, 1, i)
        ids = rng.integers(0, vocab_size, (b, s), dtype=np.int32)
        split = rng.integers(s // 4, 3 * s // 4, (b, 1))
        tt = (np.arange(s)[None, :] >= split).astype(np.int32)
        labels = np.full((b, s), -1, np.int32)
        for r in range(b):
            at = rng.choice(s, n_mask, replace=False)
            labels[r, at] = ids[r, at]
        out.append({"input_ids": ids, "token_type_ids": tt,
                    "attention_mask": np.ones((b, s), np.int32),
                    "masked_lm_labels": labels})
    return out


class Schedule:
    """The shared request schedule of a closed-loop serving mix: request
    ``k`` of the run is one entry of the mix's table of (prompt, output)
    lengths, and every cycle of ``len(table)`` requests walks the whole
    table, so any two seeds serve the same multiset of lengths per cycle,
    in another order and with other token ids.  ``mix["blocks"]`` cuts the
    table into blocks of entries that are served together: per seed and
    cycle the blocks come in a fresh order and so do the entries inside
    each, so the load is as even along a cycle as the blocks are alike
    (one block of everything is a plain shuffle)."""

    def __init__(self, mix, vocab_size, seed):
        self.table = [(int(p), int(o)) for p, o in mix["table"]]
        self.blocks = [list(map(int, b)) for b in mix["blocks"]]
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        if sorted(k for b in self.blocks for k in b) \
                != list(range(len(self.table))):
            raise ValueError("mix 'blocks' do not hold every entry of the "
                             "table exactly once")
        self._cycle = (None, None)

    def _order(self, cycle):
        if self._cycle[0] != cycle:
            rng = _rng(self.seed, 2, cycle)
            order = [k for b in rng.permutation(len(self.blocks))
                     for k in rng.permutation(self.blocks[b])]
            self._cycle = (cycle, order)
        return self._cycle[1]

    def lengths(self, k):
        cycle, at = divmod(int(k), len(self.table))
        return self.table[self._order(cycle)[at]]

    def request(self, k):
        """(prompt token ids, max_new_tokens) of the run's k-th request."""
        p, o = self.lengths(k)
        ids = _rng(self.seed, 3, k).integers(0, self.vocab_size, p,
                                             dtype=np.int32)
        return ids, o
