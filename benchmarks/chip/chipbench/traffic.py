"""The one traffic generator: a closed loop of `slots` clients.

A mix file (`traffic/<name>.json`) gives the prompt length and the
distribution of generation lengths.  Seeds change the tokens and not the
work: the queue is a run of blocks, each block holding the same `block`
lengths (the distribution's quantiles, clipped to its range, the last one
at the cap) in one order for every seed.  A window may serve fewer
requests than a block holds, and which lengths it reaches sets its work,
so no seed may reorder them; the order is the bit-reversal of the sorted
quantiles, so that every run of 2**k requests from a block's start takes
one length from each of 2**k equal strata of the distribution.  The cap in
every block keeps the KV capacity, and so every compiled shape, the same
for every seed.

All requests are queued at the start.  The scheduler admits them FIFO into
freed slots, which is what `slots` clients do when each sends its next
request as soon as its last one finishes.  The queue is as long as the
chip could serve in the window if every useful operation ran at the bf16
peak, so no program can run it dry.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path

import numpy as np

from chipbench import counts
from chipbench.spec import Spec

#: first token id the generator draws; ids below it are kept for control
#: tokens (padding, BOS, EOS) that random prompts should not contain
FIRST_ID = 3


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    prompt_len: int
    median: float
    sigma: float
    lo: int
    hi: int
    block: int

    def lengths(self) -> list[int]:
        """One block of generation lengths: lognormal quantiles, clipped,
        the last one at the cap, in ascending order."""
        nd = statistics.NormalDist()
        out = [min(self.hi, max(self.lo, round(self.median * math.exp(
            self.sigma * nd.inv_cdf((i + 0.5) / self.block)))))
            for i in range(self.block)]
        out[-1] = self.hi
        return out

    def order(self) -> list[int]:
        """One block of generation lengths in queue order: quantile i goes
        to the place whose index is i's bits reversed."""
        bits = self.block.bit_length() - 1
        sorted_ = self.lengths()
        return [sorted_[int(format(j, f"0{bits}b")[::-1], 2) if bits else 0]
                for j in range(self.block)]


def load(path: Path) -> Mix:
    path = Path(path)
    raw = json.loads(path.read_text())
    if raw.get("loop") != "closed":
        raise ValueError(f"{path}: the generator drives a closed loop only")
    gen = raw["gen"]
    if gen.get("dist") != "lognormal":
        raise ValueError(f"{path}: gen.dist must be 'lognormal'")
    mix = Mix(name=path.stem, prompt_len=int(raw["prompt_len"]),
              median=float(gen["median"]), sigma=float(gen["sigma"]),
              lo=int(gen["min"]), hi=int(gen["max"]), block=int(raw["block"]))
    if not 1 <= mix.lo <= mix.hi or mix.prompt_len < 1 or mix.block < 1:
        raise ValueError(f"{path}: bad lengths {mix}")
    if mix.block & (mix.block - 1):
        raise ValueError(f"{path}: block {mix.block} is not a power of two")
    return mix


def queue_len(spec: Spec, mix: Mix, seconds: float, peak: dict) -> int:
    """Requests the chip could finish in `seconds` at its bf16 peak, plus a
    full grid, rounded up to whole blocks."""
    block = mix.lengths()
    per_req = sum(counts.request_flops(spec, mix.prompt_len, g)
                  for g in block) / len(block)
    n = math.ceil(seconds * peak["bf16_flops"] / per_req) + spec.slots
    return -(-n // mix.block) * mix.block


def generate(spec: Spec, mix: Mix, seed: int, n: int):
    """(prompts, gen_lens) for `n` requests (a multiple of the block):
    the prompts' tokens drawn from the seed, the lengths the same for
    every seed."""
    if n % mix.block:
        raise ValueError(f"{n} requests is not a whole number of blocks")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(FIRST_ID, spec.vocab, size=(n, mix.prompt_len),
                           dtype=np.int32)
    return list(prompts), mix.order() * (n // mix.block)
