"""What the window of one run served, from the scheduler's own records.

`serve` stamps every emitted token with its time since the scheduler's t0,
which falls after warm-up; the window is [0, seconds] on that clock.  Every
rate and tail here covers all the work and all the requests of the window.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import counts
from chipbench.spec import Spec


@dataclasses.dataclass
class Window:
    seconds: float
    slots: int
    prompt_len: int
    ttft: list             # first-token time of each request, or None
    token_times: list      # per request, the time of each emitted token
    status: list           # per request, the scheduler's terminal status

    @classmethod
    def of(cls, stats: dict, seconds: float, slots: int, prompt_len: int):
        return cls(seconds, slots, prompt_len, list(stats["ttft"]),
                   [list(t) for t in stats["token_times"]],
                   list(stats["status"]))

    def tokens(self) -> int:
        """Output tokens emitted inside the window."""
        return sum(1 for ts in self.token_times for t in ts
                   if t <= self.seconds)

    def admitted(self) -> list[int]:
        """Requests whose first token falls inside the window."""
        return [i for i, t in enumerate(self.ttft)
                if t is not None and t <= self.seconds]

    def closed_loop_ttft(self) -> list[float]:
        """Time to first token of each admitted request, from the moment
        its client sent it: t0 for the first `slots` requests, else the
        finish of the request whose slot it takes.  Finishes are taken in
        time order, and the k-th one sends request `slots + k`, which is
        the order FIFO admission into freed slots serves them in."""
        finishes = sorted(ts[-1] for ts, st in zip(self.token_times,
                                                    self.status)
                          if st == "ok" and ts)
        out = []
        for i in self.admitted():
            sent = 0.0 if i < self.slots else finishes[i - self.slots]
            out.append(self.ttft[i] - sent)
        return out

    def itl_gaps(self) -> list[float]:
        """Gaps between consecutive tokens of one request, both inside the
        window, over every request."""
        out = []
        for ts in self.token_times:
            inside = [t for t in ts if t <= self.seconds]
            out.extend(b - a for a, b in zip(inside, inside[1:]))
        return out

    def prefill_launches(self) -> int:
        """Admission prefills inside the window.  Prompts share one length,
        so each admission round is one prefill launch, and the requests it
        admits share its first-token time."""
        return len({self.ttft[i] for i in self.admitted()})

    def prefill_row_use(self) -> float | None:
        """Admitted requests per row of the prefill grids launched."""
        launches = self.prefill_launches()
        if not launches:
            return None
        return len(self.admitted()) / (launches * self.slots)

    def useful_flops(self, spec: Spec) -> float:
        """Model operations of the window's real work: the prompt of every
        request admitted in it, and every token decoded in it (attention
        included, padding rows left out, logits only where computed)."""
        total = len(self.admitted()) * counts.prompt_flops(spec,
                                                           self.prompt_len)
        for ts in self.token_times:
            # token k >= 1 comes from a decode step attending plen + k keys
            for k, t in enumerate(ts[1:], start=1):
                if t <= self.seconds:
                    total += counts.decode_flops(spec, self.prompt_len + k)
        return total

    def queue_ran_dry(self, n_requests: int) -> bool:
        """True when the last queued request was admitted in the window."""
        last = self.ttft[n_requests - 1]
        return last is not None and last <= self.seconds


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None
