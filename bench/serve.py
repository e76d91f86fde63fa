"""Drives the resident serving engine through one measured window.

The engine is the system under test: requests go in through
``ServingEngine.submit()``, the engine advances by ``step()``, and tokens come
back through its ``on_token`` callback, stamped here on the host's clock.  A
closed loop keeps ``clients`` requests in flight, each client sending its
next request when the last token of its previous one arrives; an open loop
sends each request when it is due.  A request's time to first token runs
from when it was due, so a client kept waiting by a long ``step()`` is
charged for the wait.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from repro.serving import Request


@dataclasses.dataclass
class Served:
    """One request of the window and what the host saw of it."""
    index: int
    prompt: np.ndarray
    max_new: int
    due: float
    client: int = 0
    submitted: float = 0.0
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    tokens: list = dataclasses.field(default_factory=list)
    token_t: list = dataclasses.field(default_factory=list)
    request: Optional[Request] = None

    @property
    def complete(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class Window:
    """What one window served: every request, and the window's bounds."""
    t0: float
    t1: float
    served: list
    token_times: list
    lateness_s: list


def no_span(_name):
    return contextlib.nullcontext()


def drive(engine, traffic, seconds: float, *, span: Callable = no_span,
          on_tick: Optional[Callable] = None) -> Window:
    """Serve ``traffic`` for ``seconds``, then wait for every request sent
    in the window to finish.  ``span(name)`` opens a host span around each
    call into the engine and each wait; ``on_tick(now, t0)`` runs once per
    loop turn (the traced run starts and stops its trace there)."""
    by_req: dict = {}
    token_times: list = []
    served: list = []
    lateness: list = []
    free_at: list = []  # closed loop: (time, client) of each idle client
    sent: dict = {}     # closed loop: requests each client has sent

    def on_token(req, tok):
        now = time.perf_counter()
        s = by_req[id(req)]
        if s.first_t is None:
            s.first_t = now
        s.last_t = now
        s.tokens.append(int(tok))
        s.token_t.append(now)
        token_times.append(now)
        if traffic.closed and s.complete:
            free_at.append((now, s.client))

    engine.on_token = on_token
    t0 = time.perf_counter()
    t1 = t0 + seconds
    if traffic.closed:
        free_at.extend((t0, k) for k in range(traffic.clients))

    def send(due: float, client: int = 0) -> None:
        with span("bench.generate"):
            j = sent.get(client, 0)
            sent[client] = j + 1
            spec = traffic.request(len(served), client,
                                   j if traffic.closed else None)
        s = Served(spec.index, spec.prompt, spec.max_new, due, client)
        req = Request(prompt=spec.prompt, max_new_tokens=spec.max_new,
                      temperature=0.0, seed=spec.index)
        s.request = req
        by_req[id(req)] = s
        served.append(s)
        with span("bench.submit"):
            s.submitted = time.perf_counter()
            engine.submit(req)
        lateness.append(s.submitted - due)
        if req.done:  # refused at submit: the client is free again
            free_at.append((time.perf_counter(), client))

    while True:
        now = time.perf_counter()
        if on_tick is not None:
            on_tick(now, t0)
        if now >= t1:
            break
        if traffic.closed:
            while free_at:
                send(*free_at.pop(0))
            nxt = None
        else:
            while t0 + traffic.arrival(len(served)) <= now:
                send(t0 + traffic.arrival(len(served)))
            nxt = t0 + traffic.arrival(len(served))
        if engine.has_work:
            with span("bench.step"):
                engine.step()
        elif nxt is not None:
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
    with span("bench.drain"):
        engine.drain()
    return Window(t0, t1, served, token_times, lateness)


def percentile(values, q: float) -> float:
    """The q-th percentile of all values, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(win: Window) -> dict:
    """The cell's end-to-end metrics over the whole window."""
    t0, t1 = win.t0, win.t1
    ttft = [s.first_t - s.due for s in win.served
            if s.first_t is not None and t0 <= s.first_t < t1]
    tpot = [(s.last_t - s.first_t) / (len(s.tokens) - 1)
            for s in win.served
            if s.complete and s.last_t < t1 and len(s.tokens) > 1]
    out = sum(1 for t in win.token_times if t0 <= t < t1)
    return {
        "ttft_p95_ms": percentile(ttft, 95) * 1e3 if ttft else None,
        "tpot_p95_ms": percentile(tpot, 95) * 1e3 if tpot else None,
        "output_tok_s": out / (t1 - t0),
        "n_ttft": len(ttft), "n_tpot": len(tpot), "n_tokens": out,
    }
