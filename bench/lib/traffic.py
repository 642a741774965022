"""The one traffic generator; every mix is a data file under
``bench/traffic/`` that it reads.

Serving (``kind: serve``): a closed loop of ``clients`` callers over an
engine with ``slots`` slots.  A caller sends its next request when its
previous reply is complete, so with ``clients`` a multiple of ``slots``
the engine's first-in first-out queue forms wave ``w`` from requests
``w*slots .. (w+1)*slots - 1``.  Request sizes come from a fixed table of
``cycle_waves * slots`` draws made with the mix's own ``size_seed``, and
request ``k`` takes entry ``k mod`` the table's length: every run seed
serves the same sizes, wave by wave.  The run seed orders the sizes
inside each wave and draws every token id, so two seeds differ in their
inputs and not in their work.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np


def _u64(seed: int) -> int:
    """Any whole number as numpy seed entropy."""
    return int(seed) % (1 << 64)


def _lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return rng.integers(spec["lo"], spec["hi"] + 1, n)


@dataclass(frozen=True)
class ServeRequest:
    index: int          # global send order
    prompt: np.ndarray  # int32 token ids
    max_new: int


class ClosedLoop:
    """Requests of a closed-loop serving mix, by global send order."""

    def __init__(self, traffic: Dict, seed: int, vocab: int):
        if traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        self.clients = int(traffic["clients"])
        self.slots = int(traffic["slots"])
        self.max_len = int(traffic["max_len"])
        if self.clients % self.slots:
            raise ValueError("clients must be a multiple of slots")
        self.seed = _u64(seed)
        self.vocab = vocab
        n = int(traffic["cycle_waves"]) * self.slots
        rng = np.random.default_rng(int(traffic["size_seed"]))
        self.prompt_lens = _lengths(traffic["prompt_tokens"], n, rng)
        self.out_lens = _lengths(traffic["output_tokens"], n, rng)
        longest = int(self.prompt_lens.max() + self.out_lens.max())
        if longest > self.max_len:
            raise ValueError(f"prompt + output reaches {longest} tokens, "
                             f"over max_len {self.max_len}")
        self._perm: Dict[int, np.ndarray] = {}

    @property
    def cycle(self) -> int:
        return len(self.prompt_lens)

    def _size(self, k: int) -> Tuple[int, int]:
        wave, slot = divmod(k, self.slots)
        if wave not in self._perm:
            rng = np.random.default_rng([self.seed, 1, wave])
            self._perm[wave] = rng.permutation(self.slots)
        j = (wave * self.slots + int(self._perm[wave][slot])) % self.cycle
        return int(self.prompt_lens[j]), int(self.out_lens[j])

    def request(self, k: int) -> ServeRequest:
        plen, out = self._size(k)
        rng = np.random.default_rng([self.seed, 2, k])
        prompt = rng.integers(0, self.vocab, plen).astype(np.int32)
        return ServeRequest(k, prompt, out)

    def wave_prompt_lens(self) -> List[int]:
        """The padded prompt length of every wave of one cycle (the
        engine pads a wave to its longest prompt)."""
        return [int(self.prompt_lens[w * self.slots:(w + 1) * self.slots]
                    .max()) for w in range(self.cycle // self.slots)]

    def warm_prompts(self, plen: int, index: int) -> List[np.ndarray]:
        """A full wave of prompts padded to ``plen`` (set-up only)."""
        rng = np.random.default_rng([self.seed, 3, index])
        lens = [plen] + [int(x) for x in rng.integers(1, plen + 1,
                                                      self.slots - 1)]
        return [rng.integers(0, self.vocab, n).astype(np.int32)
                for n in lens]

