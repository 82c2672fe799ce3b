"""Arrival process `closed`: `clients` clients, each sending its next
request when its last is answered, timed from that answer (the first
from the window's opening).  Each client holds `rounds` requests, the
most a run can send; request i goes to client i mod `clients`."""
import collections


def count(stream, seconds):
    return int(stream["clients"]) * int(stream["rounds"])


def place(requests, stream, seconds, shape):
    for i, req in enumerate(requests):
        req.client = i % int(stream["clients"])


def warm_sizes(stream, batch):
    """The clients' requests are drafted together: the largest power of
    two up to both the clients and `batch`."""
    top = min(int(batch), int(stream["clients"]))
    return [1 << (top.bit_length() - 1)]


class Source:
    """The window's view of the clients: who is ready since when."""

    def __init__(self, requests):
        self.queues = collections.defaultdict(collections.deque)
        for req in requests:
            self.queues[req.client].append(req)
        self.clients = sorted(self.queues)
        self.ready = {}

    def start(self, t0):
        self.ready = {c: t0 for c in self.clients}

    def due(self, now):
        out = []
        for c in sorted(self.ready):
            if self.queues[c]:
                out.append((self.queues[c].popleft(), self.ready.pop(c)))
        return out

    def answered(self, rec):
        self.ready[rec["client"]] = rec["done"]

    def next_due(self):
        return None          # the next request waits for an answer

    def late(self):
        return []

    def exhausted(self):
        """Clients that sent every request drawn for them."""
        return sum(1 for c in self.ready if not self.queues[c])
