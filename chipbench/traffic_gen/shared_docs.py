"""The generator of closed-loop question traffic over a pinned corpus:
every request is one of a few long DOCUMENTS followed by a fresh short
question, so the document's rows are served from the server's prefix
tree and only the question is prefilled.

What it adds to the contract of chipbench/traffic_gen/requests.py (the
driver still asks only `poll`, `finished`, `ramp_done` and `frame`, and
of a request `k`, `prompt`, `max_new`, `due_s`):

- `documents`: their count and the distribution of their lengths,
  drawn from the file's `length_seed` and rounded to `round_to` (the
  cache's block size, so that a document is a whole number of blocks
  and matches whole). Their token ids come from --seed, like every id.
- a ramp in THREE phases, all set-up: (1) one LOADER request a document
  (the document alone, one output token), one after another: the
  server prefills it once and publishes its blocks when the loader
  retires; (2) once every
  loader has finished, `callers` callers are released `stagger_steps`
  apart; (3) the window opens `life_steps` (one mean request life)
  after the last release. `ramp_done` is true only then: every
  document published AND every caller released AND the life elapsed.
- a request carries `doc` (which document; the same seeded uniform
  order for every --seed) and `question` (its fresh tokens' count)
  beside the contract's keys; `documents_tokens(k)` gives the length
  of request k's document (the loaders': 0, nothing is matched then).

Lengths and order are the same for every --seed; --seed makes only the
token ids, drawn from [1, vocab).
"""

from __future__ import annotations

from typing import List

import numpy as np

from chipbench.traffic_gen.requests import _draw


class SharedDocs:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        kind = traffic.get("arrival", {"kind": "closed"})["kind"]
        if kind != "closed":
            raise ValueError(f"arrival kind {kind!r}: this generator is "
                             "a closed loop")
        self.traffic, self.seed, self.vocab = traffic, int(seed), int(vocab)
        docs = traffic["documents"]
        self.n_docs = int(docs["count"])
        cycle = int(traffic.get("length_cycle", 2048))
        rng = np.random.default_rng(int(traffic["length_seed"]))
        step = int(docs.get("round_to", 1))
        lens = _draw(rng, docs["tokens"], self.n_docs)
        self.doc_lens = [max(step, int(round(n / step)) * step)
                         for n in lens]
        self.order = rng.integers(0, self.n_docs, cycle)
        self.questions = _draw(rng, traffic["question_tokens"], cycle)
        self.outs = _draw(rng, traffic["output_tokens"], cycle)
        self.docs = [
            [int(t) for t in np.random.default_rng(
                [self.seed, 1 << 20, d]).integers(1, self.vocab, n)]
            for d, n in enumerate(self.doc_lens)]
        self.callers = int(traffic["callers"])
        self.stagger = int(traffic.get("stagger_steps", 0))
        self.life = int(traffic["life_steps"])
        self.issued = 0             # loaders first, then questions
        self.outstanding = 0
        self.loaders_left = self.n_docs
        self.released_at = None     # the step the first caller went

    # -- what a request is ---------------------------------------------
    def lengths(self, k: int):
        """(document, question tokens, output tokens) of request k."""
        if k < self.n_docs:
            return k, 0, 1
        i = (k - self.n_docs) % len(self.order)
        return int(self.order[i]), int(self.questions[i]), int(self.outs[i])

    def document_tokens(self, k: int) -> int:
        """The rows of request k that a published document can serve."""
        return 0 if k < self.n_docs else self.doc_lens[self.lengths(k)[0]]

    def request(self, k: int) -> dict:
        d, nq, out = self.lengths(k)
        q = np.random.default_rng([self.seed, k]).integers(
            1, self.vocab, nq)
        return {"k": k, "prompt": self.docs[d] + [int(t) for t in q],
                "max_new": out, "due_s": None, "doc": d, "question": nq}

    # -- the driver's four questions -----------------------------------
    def poll(self, step: int, now_s: float) -> List[dict]:
        if self.loaders_left:                   # phase 1: the loaders,
            if self.issued >= self.n_docs:      # one at a time (each
                return []                       # holds a whole scratch)
            want = 1
        else:                                   # phase 2 on: callers
            if self.released_at is None:
                self.released_at = step
            since = step - self.released_at
            want = self.callers if not self.stagger else min(
                self.callers, since // self.stagger + 1)
        out = []
        while self.outstanding < want:
            out.append(self.request(self.issued))
            self.issued += 1
            self.outstanding += 1
        return out

    def finished(self, n: int = 1) -> None:
        self.outstanding -= n
        if self.loaders_left:
            # only loaders are out while any is: a loader that finished
            # has retired, and its document's blocks are published
            self.loaders_left -= n

    def ramp_done(self, step: int, now_s: float) -> bool:
        if self.loaders_left or self.released_at is None:
            return False
        last = self.released_at + (self.callers - 1) * self.stagger
        return step >= last + self.life

    def frame(self):
        """(longest document ++ question ++ output, longest output)."""
        out = int(self.traffic["output_tokens"]["max"])
        return (max(self.doc_lens)
                + int(self.traffic["question_tokens"]["max"]) + out, out)


def make(traffic: dict, seed: int, **sizes) -> SharedDocs:
    return SharedDocs(traffic, seed, sizes["vocab"])
