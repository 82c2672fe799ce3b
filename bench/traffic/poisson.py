"""Arrival process `poisson`: an open loop.  A window of s seconds
holds exactly round(`rate_per_s` * s) requests, at arrival times from
one fixed set of exponential gaps (the distribution's quantiles, scaled
to sum to s, in an order drawn from the fixed shape stream).  A request
is timed from when it was due; one still unsent at the close is sent
after it (`late`)."""
import collections

import numpy as np


def count(stream, seconds):
    return int(round(float(stream["rate_per_s"]) * seconds))


def place(requests, stream, seconds, shape):
    for req, t in zip(requests, arrivals(
            len(requests), float(stream["rate_per_s"]), seconds, shape)):
        req.due = float(t)


def arrivals(n, rate, seconds, rng):
    """Arrival times of n requests in [0, seconds)."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()          # the same scale for every seed
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def warm_sizes(stream, batch):
    """Every batch size the loop can draft: powers of two up to `batch`."""
    return [1 << i for i in range(int(batch).bit_length())]


class Source:
    """The window's view of the stream: what is due when."""

    def __init__(self, requests):
        self.todo = collections.deque(requests)
        self.t0 = 0.0

    def start(self, t0):
        self.t0 = t0

    def due(self, now):
        out = []
        while self.todo and self.t0 + self.todo[0].due <= now:
            req = self.todo.popleft()
            out.append((req, self.t0 + req.due))
        return out

    def answered(self, rec):
        pass

    def next_due(self):
        return self.t0 + self.todo[0].due if self.todo else None

    def late(self):
        out = [(r, self.t0 + r.due) for r in self.todo]
        self.todo.clear()
        return out

    def exhausted(self):
        return 0
