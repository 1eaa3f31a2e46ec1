"""Training data for the cells, made from the run's seed.

``MarkovLM`` and ``tokens`` are a copy of the program's synthetic
stream (``repro.data.synthetic``): a seeded first-order Markov chain
with Zipf-weighted successors.  The copy is what the reference reads,
so it must stay bit-for-bit what the program's own generator yields for
the same seed, step and rows (the SPMD trainer draws its batches from
the program's generator; the parameter-server cells are fed from here).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np


def sub_seeds(seed: int) -> Dict[str, int]:
    """Fold a run seed of any size into the seeds each part takes: a
    32-bit key for the weights and a 31-bit seed for the data chain."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return {"weights": int(words[0]), "data": int(words[1]) % (2**31 - 1)}


class MarkovLM:
    """Vocab-sized first-order chain with Zipf-weighted successors."""

    def __init__(self, vocab_size: int, seq_len: int, seed: int,
                 branching: int = 32):
        self.vocab_size, self.seq_len, self.seed = vocab_size, seq_len, seed
        self.branching = branching
        rng = np.random.RandomState(seed)
        self.successors = rng.randint(0, vocab_size,
                                      size=(vocab_size, branching))
        w = 1.0 / np.arange(1, branching + 1) ** 1.2
        self.weights = (w / w.sum()).astype(np.float64)

    def sample_rows(self, step: int, rows) -> np.ndarray:
        """Tokens (len(rows), seq_len + 1) for global row ids at a step."""
        out = np.empty((len(rows), self.seq_len + 1), np.int32)
        for i, r in enumerate(rows):
            rng = np.random.RandomState(
                (self.seed * 1_000_003 + step * 7_919 + int(r)) % 2**31)
            tok = rng.randint(self.vocab_size)
            choices = rng.choice(self.branching, size=self.seq_len + 1,
                                 p=self.weights)
            for t in range(self.seq_len + 1):
                out[i, t] = tok
                tok = self.successors[tok, choices[t]]
        return out


def tokens(chain: MarkovLM, step: int, rows) -> Dict[str, np.ndarray]:
    """One batch: inputs and next-token labels."""
    toks = chain.sample_rows(step, rows)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def worker_rows(worker: int, batch: int) -> List[int]:
    """Worker ``worker``'s rows of every step: no two workers share one."""
    return list(range(worker * batch, (worker + 1) * batch))


class Feed:
    """Per-worker batch iterators for the parameter-server engine, with
    a barrier over the first ``hold`` batches.

    Batch ``k`` (k < hold) is handed out only once every worker has
    asked for it.  A worker asks after its previous push returned and
    its next pull is done, so while all are waiting the server state is
    quiescent: ``on_hold(k)`` reads it there, and the pulls behind step
    ``k`` all saw the same state.  Batches from ``hold`` on flow freely.
    """

    def __init__(self, chain: MarkovLM, workers: int, batch: int, *,
                 hold: int = 0, to_device: Callable = lambda b: b,
                 on_hold: Optional[Callable[[int], None]] = None):
        self.chain, self.workers, self.batch = chain, workers, batch
        self.hold, self.to_device = hold, to_device
        self.on_hold = on_hold or (lambda k: None)
        self._cond = threading.Condition()
        self._asked = [0] * hold          # workers waiting on batch k
        self._open = [False] * hold
        self.closed = False
        #: what ``on_hold`` raised, if it did (the run cannot go on)
        self.error: Optional[BaseException] = None

    def close(self) -> None:
        """Release every waiting worker (the run is ending)."""
        with self._cond:
            self.closed = True
            self._cond.notify_all()

    def _wait_turn(self, k: int) -> None:
        with self._cond:
            self._asked[k] += 1
            if self._asked[k] == self.workers:
                self._cond.release()
                try:
                    self.on_hold(k)
                except BaseException as e:
                    self.error, self.closed = e, True
                    raise
                finally:
                    self._cond.acquire()
                    self._open[k] = True
                    self._cond.notify_all()
            while not self._open[k] and not self.closed:
                self._cond.wait(timeout=1.0)

    def __call__(self, worker: int) -> Iterator[Dict]:
        rows = worker_rows(worker, self.batch)
        step = 0
        while True:
            if step < self.hold:
                self._wait_turn(step)
            yield self.to_device(tokens(self.chain, step, rows))
            step += 1
