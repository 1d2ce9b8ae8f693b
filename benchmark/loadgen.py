"""The one general traffic generator.  A mix is a data file of
parameters under ``traffic/``: its ``queries`` are the ordered list it
sends, and its ``loop`` names the loop here that reads the rest.

``closed_passes``: ``clients`` closed-loop clients, each sending the
mix's ordered query list over and over and waiting for each answer.
One *pass* is one trip through the list.  The seed rotates the starting
point of the window's passes (``Statements.order``) and sets the run of
trailing spaces each statement carries, so that every statement's text
is new to the server (parse, bind and plan run every time;
``QueryRunner._plans`` is keyed by text) while structure and literals
stay fixed (no program is new).  The same seed gives the same
statements in the same order.  The seed does not order the warm-up:
``run.py`` sends that in the mix's own list order for every seed and
takes its texts from the same ``Statements.text``, so a process starts
alike whatever the seed and no text repeats (PERF.md, PR 33).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

from benchmark.specs import Query


@dataclasses.dataclass
class QueryRecord:
    name: str
    seq: int  # unique in the process, warm-up included
    client_ms: float  # POST to last row decoded, on the client
    t0: float  # perf_counter at the POST
    ok: bool
    why: Optional[str] = None  # what failed
    stats: dict = dataclasses.field(default_factory=dict)  # final page
    query_id: Optional[str] = None
    spans: list = dataclasses.field(default_factory=list)  # traced run


@dataclasses.dataclass
class Pass:
    client: int
    index: int
    end: float  # perf_counter at the last answer
    queries: List[QueryRecord]

    @property
    def ms(self) -> float:
        return sum(q.client_ms for q in self.queries)

    @property
    def ok(self) -> bool:
        return all(q.ok for q in self.queries)


class Statements:
    """One client's statements: the window's rotated pass order, and
    for each query the next text no earlier statement had, warm-up
    included."""

    def __init__(self, mix: dict, queries: List[Query], seed: int,
                 client: int = 0):
        self.clients = int(mix["clients"])
        self.client = client
        k = (seed + client) % len(queries) if mix["rotate_start_by_seed"] else 0
        self.order = list(queries[k:]) + list(queries[:k])
        pad = mix["trailing_spaces"]
        self.base = seed % int(pad["base_from_seed_below"])
        self.step = int(pad["step_per_statement"])
        self._sent = {q.name: 0 for q in queries}

    def text(self, query: Query) -> str:
        n = self._sent[query.name]
        self._sent[query.name] = n + 1
        spaces = self.base + (n * self.clients + self.client) * self.step
        return query.sql + " " * spaces


def closed_passes(mix: dict, statements: List[Statements], seconds: float,
                  submit: Callable[[int, Query, str], QueryRecord],
                  boundary: Callable[[int, int], None]):
    """Run the closed loop for ``seconds``.  ``submit(client, query,
    text)`` sends one statement and returns its record;
    ``boundary(client, passes_done)`` is called before each pass of
    each client (the traced run starts and stops the profiler there).
    Returns (window start, completed passes, queries attempted, their
    records).  A pass still running when the window closes is not
    counted: its client stops after the query in flight."""
    t0 = time.perf_counter()
    deadline = t0 + seconds
    passes: List[Pass] = []
    records: List[QueryRecord] = []
    lock = threading.Lock()

    def client_loop(c: int):
        index = 0
        while time.perf_counter() < deadline:
            boundary(c, index)
            done = []
            for query in statements[c].order:
                if time.perf_counter() >= deadline:
                    break
                rec = submit(c, query, statements[c].text(query))
                done.append(rec)
            end = time.perf_counter()
            with lock:
                records.extend(done)
                if len(done) == len(statements[c].order) and end <= deadline:
                    passes.append(Pass(c, index, end, done))
            index += 1
        boundary(c, index)

    if len(statements) == 1:
        client_loop(0)
    else:
        threads = [threading.Thread(target=client_loop, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(len(statements))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return t0, passes, records


LOOPS = {"closed_passes": closed_passes}
