"""WordCount over HiBench's generated text (i2MapReduce, arXiv:1501.04854,
Sec. 3, on the input of the HiBench micro WordCount).

The system under test is the engine's own job declaration
(``repro.apps.wordcount``): Map emits <word, 1.0> per token, Reduce sums
in float32.  What the benchmark owns is here: the corpus, the rewrite
events, the plain reference and the comparison.

- Corpus: ``documents`` records as Hadoop's RandomTextWriter writes them
  for HiBench: each holds ``min_words`` to ``max_words - 1`` words (its
  length uniform), each word drawn uniformly from a list of ``vocab``
  words.  Word ids stand for the words; a record is a row of
  ``max_words - 1`` ids padded with -1.
- Event ``rewrite``: one document is replaced: a '-' row with its
  current words, then a '+' row with a fresh record.  Traffic ``choice``
  ``permutation`` rewrites every document once, in an order drawn from
  the seed, before any again, so a run of consecutive events never
  repeats a document and every batch has the same row count.
- Reference: per-word counts of the corpus after every applied event,
  exact (integers).  The engine's float32 sums of 1.0 are exact below
  2**24 per word, so the comparison is exact: the number of words whose
  count differs, with the limit 0.
- Control: the same reference counted in bfloat16, the precision below
  the configuration's float32 value column.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from perfbench.harness.schedule import seed_seq

VALUE_WIDTH = 1                        # one float32 column, "c"


class Job:
    rows_per_event = 2
    value_width = VALUE_WIDTH

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.api import RunConfig, StreamConfig
        from repro.apps import wordcount as wc
        if traffic["event"] != "rewrite" or traffic["choice"] != "permutation":
            raise ValueError(f"wordcount has no event {traffic['event']!r} "
                             f"with choice {traffic['choice']!r}")
        self.vocab = int(cfg["vocab"])
        self.min_words = int(cfg["min_words"])
        self.width = int(cfg["max_words"]) - 1
        n_docs = int(cfg["documents"])
        self.docs0 = self.texts(np.random.default_rng(seed_seq(seed, 1)),
                                n_docs)
        self.spec, self.data = wc.make_job(self.docs0, self.vocab)
        self.describe = (f"{n_docs} documents of {self.min_words}-"
                         f"{self.width} words ({int((self.docs0 >= 0).sum())}"
                         f" in all), vocabulary {self.vocab}")
        self.run_config = RunConfig(**cfg["run_config"])
        self.stream_config = StreamConfig(**cfg["stream_config"])
        self.rng = np.random.default_rng(seed_seq(seed, 2))
        self.ev_doc = np.zeros(0, np.int64)
        self.ev_new = np.zeros((0, self.width), np.int32)

    def texts(self, rng, n: int) -> np.ndarray:
        """``n`` records: rows of word ids, -1 past each record's length."""
        words = rng.integers(0, self.vocab, (n, self.width), dtype=np.int32)
        lengths = rng.integers(self.min_words, self.width + 1, n)
        words[np.arange(self.width)[None, :] >= lengths[:, None]] = -1
        return words

    def records(self, n: int) -> List:
        """Events 0..n-1 as DeltaRecords, in order, epoch = sequence."""
        from repro.stream.source import DeltaRecord
        m = len(self.docs0)
        docs = np.concatenate([self.rng.permutation(m)
                               for _ in range(-(-n // m))])[:n]
        new = self.texts(self.rng, n)
        # each event's '-' row: the document's previous event, else its
        # initial text
        order = np.argsort(docs, kind="stable")
        prev = np.full(n, -1)
        same = docs[order[1:]] == docs[order[:-1]]
        prev[order[1:][same]] = order[:-1][same]
        old = np.where((prev >= 0)[:, None], new[prev], self.docs0[docs])
        rows = np.stack([old, new], axis=1)
        ids = np.repeat(docs.astype(np.int32), 2).reshape(n, 2)
        sign = np.array([-1, 1], np.int8)
        out = [DeltaRecord(ids[i], {"w": rows[i]}, sign, epoch=i)
               for i in range(n)]
        self.ev_doc, self.ev_new = docs, new
        return out

    def corpus(self, applied: int) -> np.ndarray:
        """The corpus after events 0..applied-1."""
        docs = self.docs0.copy()
        rev = self.ev_doc[:applied][::-1]
        ids, at = np.unique(rev, return_index=True)   # each doc's last event
        docs[ids] = self.ev_new[applied - 1 - at]
        return docs

    def words(self, applied: int) -> np.ndarray:
        w = self.corpus(applied).ravel()
        return w[w >= 0]

    def reference(self, applied: int) -> Dict[str, np.ndarray]:
        return {"c": np.bincount(self.words(applied), minlength=self.vocab)
                .astype(np.float64)}

    def control(self, applied: int) -> Dict[str, np.ndarray]:
        """The reference counted in bfloat16, on the default device."""
        import jax.numpy as jnp
        words = jnp.asarray(self.words(applied))
        c = jnp.zeros(self.vocab, jnp.bfloat16).at[words].add(
            jnp.ones(words.shape, jnp.bfloat16))
        return {"c": np.asarray(c.astype(jnp.float32), np.float64)}

    def compare(self, result: Dict[str, np.ndarray],
                ref: Dict[str, np.ndarray]) -> Dict[str, tuple]:
        got = np.asarray(result["c"], np.float64)
        return {"count_mismatches":
                (int(np.count_nonzero(got != ref["c"])), 0)}
