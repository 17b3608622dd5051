"""The generator of closed-loop request traffic for a served model.

A mix is a data file of chipbench/traffic/: lengths, callers, the
ramp. Every --seed gets the SAME list of (prompt, output) lengths in
the same order (drawn from the file's `length_seed`); --seed makes only
the token ids. So runs with different seeds do the same amount of work
in the same order.

`callers` callers each submit their next request the moment their last
one finished. Caller i is released at step i * stagger_steps of the
ramp, and the ramp (set-up) ends after `ramp_steps` calls of step().

Open-loop arrivals (rates, bursts) and shared prefixes are not here:
no cell uses them yet, and nothing of a yardstick file is kept that no
chip run has proven. A mix that needs them names a generator file of
its own under `generator`; the driver asks of a generator only
`poll`, `finished`, `ramp_done` and `frame`, and of a request `k`,
`prompt`, `max_new` and `due_s` (None: TTFT from submit(); a number of
seconds since the ramp began: TTFT from that due time).
"""

from __future__ import annotations

import math
from typing import List

import numpy as np


def _draw(rng, spec: dict, n: int) -> np.ndarray:
    dist, lo, hi = spec["dist"], spec["min"], spec["max"]
    if dist == "uniform":
        return rng.integers(lo, hi + 1, n)
    if dist == "log_uniform":
        v = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
        return np.clip(np.rint(v), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


class Requests:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        kind = traffic.get("arrival", {"kind": "closed"})["kind"]
        if kind != "closed":
            raise ValueError(f"arrival kind {kind!r}: this generator is "
                             "the closed loop; name another `generator`")
        self.traffic, self.seed, self.vocab = traffic, int(seed), int(vocab)
        cycle = int(traffic.get("length_cycle", 512))
        rng = np.random.default_rng(int(traffic["length_seed"]))
        self.plens = _draw(rng, traffic["prompt_tokens"], cycle)
        self.outs = _draw(rng, traffic["output_tokens"], cycle)
        self.callers = int(traffic["callers"])
        self.stagger = int(traffic.get("stagger_steps", 0))
        self.issued = 0
        self.outstanding = 0

    def lengths(self, k: int):
        i = k % len(self.plens)
        return int(self.plens[i]), int(self.outs[i])

    def request(self, k: int) -> dict:
        plen, out = self.lengths(k)
        rng = np.random.default_rng([self.seed, k])
        prompt = rng.integers(1, self.vocab, plen)
        return {"k": k, "prompt": [int(t) for t in prompt],
                "max_new": out, "due_s": None}

    def poll(self, step: int, now_s: float) -> List[dict]:
        """Requests to submit now: `step` counts step() calls since the
        ramp began (`now_s`, the seconds since then, is for open-loop
        generators)."""
        released = self.callers if not self.stagger else min(
            self.callers, step // self.stagger + 1)
        out = []
        while self.outstanding < released:
            out.append(self.request(self.issued))
            self.issued += 1
            self.outstanding += 1
        return out

    def finished(self, n: int = 1) -> None:
        self.outstanding -= n

    def ramp_done(self, step: int, now_s: float) -> bool:
        return step >= int(self.traffic["ramp_steps"])

    def frame(self):
        """(longest prompt ++ output, longest output) of the mix: the
        fixed frame the reference pads to, so it compiles once."""
        out = int(self.traffic["output_tokens"]["max"])
        return int(self.traffic["prompt_tokens"]["max"]) + out, out


def make(traffic: dict, seed: int, **sizes) -> Requests:
    return Requests(traffic, seed, sizes["vocab"])
