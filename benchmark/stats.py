"""Percentile and rate arithmetic over the harness's own token times.

Everything here works on plain lists of host-clock seconds; nothing reads
the program's histograms. A request is a :class:`ReqLog`: when it was
submitted, when each of its tokens reached the host, and how it ended.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class ReqLog:
    """One request as the load generator saw it (host clock, seconds)."""

    client: int
    prompt_len: int
    max_new: int
    submit_t: float
    token_t: list = dataclasses.field(default_factory=list)
    #: The loop iteration (``server.step()`` call) that streamed each token.
    token_step: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finish_t: float | None = None
    finish_reason: str | None = None
    rejected: str | None = None
    prompt: list | None = None

    @property
    def ok(self) -> bool:
        return (self.rejected is None and self.finish_reason == "ok"
                and len(self.tokens) == self.max_new)


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest ranks (numpy's default), None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tokens_between(reqs, t_open: float, t_close: float) -> int:
    return sum(1 for r in reqs for t in r.token_t if t_open <= t <= t_close)


def out_tokens_per_s(reqs, t_open: float, t_close: float) -> float:
    """Output tokens a second over the window: all the work and all the
    time. Tokens reach the host in bursts, one a loop iteration (a decode
    chunk's, with the first tokens of the prompts prefilled before it). A
    burst's tokens are laid evenly over the time since the burst before it,
    which is the time the server took to make them, and the part of that
    time inside the window counts. So the rate does not jump by a burst
    with the instant at which the window closes (with whole bursts counted,
    every seed read one of two rates, 0.54 % apart), and a stall before
    the close still lowers it: the burst it delays counts for little."""
    when: dict[int, float] = {}
    count: dict[int, int] = {}
    for r in reqs:
        for t, step in zip(r.token_t, r.token_step):
            when[step] = max(when.get(step, t), t)
            count[step] = count.get(step, 0) + 1
    total, start = 0.0, t_open
    for step in sorted(when):
        end = when[step]
        start = min(start, end)
        inside = min(end, t_close) - max(start, t_open)
        if end > start:
            total += count[step] * max(inside, 0.0) / (end - start)
        elif t_open <= end <= t_close:
            total += count[step]
        start = end
    return total / (t_close - t_open)


def in_window(reqs, t_open: float, t_close: float) -> list:
    return [r for r in reqs if t_open <= r.submit_t <= t_close]


def ttft_ms(reqs, t_open: float, t_close: float, t_drain_end: float) -> list:
    """Milliseconds from submit to first token, one for every request
    submitted inside the window. One that failed, was rejected or had no
    first token when the drain ended counts at the drain's end."""
    out = []
    for r in in_window(reqs, t_open, t_close):
        missed = r.rejected is not None or not r.token_t or (
            r.finish_reason not in (None, "ok"))
        end = t_drain_end if missed else r.token_t[0]
        out.append((end - r.submit_t) * 1e3)
    return out


def tpot_ms(reqs, t_open: float, t_close: float) -> list:
    """Milliseconds a token after the first, one for every request submitted
    inside the window that streamed at least two."""
    return [
        (r.token_t[-1] - r.token_t[0]) / (len(r.token_t) - 1) * 1e3
        for r in in_window(reqs, t_open, t_close) if len(r.token_t) >= 2
    ]


def burst_periods_ms(reqs, first_step: int, last_step: int) -> list:
    """Milliseconds between successive token bursts of the decode batch: a
    burst is one loop iteration that streamed a token other than a
    request's first (a decode chunk reaching the host)."""
    when: dict[int, float] = {}
    for r in reqs:
        for i, (t, s) in enumerate(zip(r.token_t, r.token_step)):
            if i >= 1 and first_step <= s <= last_step:
                when[s] = max(when.get(s, t), t)
    ts = [when[s] for s in sorted(when)]
    return [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
