"""The one general traffic generator and the two loops that offer it.

A traffic file gives distributions, clips, a rate or a client count.  The
generator draws the SET of request sizes and the SET of arrival gaps
from the file's `population_seed`, and `--seed` decides their ORDER, the
token ids and (elsewhere) the weights.  So every seed offers the same
requests and the same gaps in another order: the work of a window is
the same, no run replays another seed's schedule, and the schedule is a
pure function of (file, seed, seconds).  A closed loop's pool is that
set repeated, each repeat in an order of its own, so a run that gets
through more of the pool still meets the same sizes.  The loops time
each request from when it was DUE and report how late it left.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due_s: float            # offset from the start of the ramp
    prompt: list[int]
    out_len: int
    # filled by the loop (perf_counter offsets from the ramp start):
    sent_s: float | None = None
    first_s: float | None = None
    last_s: float | None = None
    n_tokens: int = 0
    tokens: list[int] | None = None
    error: str | None = None
    trace_id: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.n_tokens == self.out_len


def _draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """n integer lengths from {"dist", ..., "clip": [lo, hi]}."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["low"], spec["high"], n)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec["clip"]
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gaps(rng: np.random.Generator, arrivals: dict, n: int,
          total_s: float) -> np.ndarray:
    """n inter-arrival gaps that sum to total_s: exponential (Poisson)
    or gamma with coefficient of variation `cv` (bursts)."""
    proc = arrivals["process"]
    if proc == "poisson":
        g = rng.exponential(1.0, n)
    elif proc == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / shape, n)
    elif proc == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return g * (total_s / g.sum())


def sizes(traffic: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The set of n (prompt_len, out_len) pairs, in the file's order."""
    pop = np.random.default_rng(traffic["population_seed"])
    return (_draw(pop, traffic["prompt_len"], n),
            _draw(pop, traffic["output_len"], n))


def _requests(traffic: dict, vocab: int, seed: int, due: np.ndarray,
              plen: np.ndarray, olen: np.ndarray) -> list[Request]:
    rng = np.random.default_rng([seed, 2])
    share = traffic.get("sharing") or {}
    prefix = rng.integers(0, vocab, int(share.get("prefix_len", 0))).tolist()
    out = []
    for i, (d, p, o) in enumerate(zip(due, plen, olen)):
        toks = rng.integers(0, vocab, int(p)).tolist()
        if prefix and rng.random() < share.get("share", 0.0):
            toks = (prefix + toks)[:int(p)]
        out.append(Request(i, float(d), toks, int(o)))
    return out


def open_schedule(traffic: dict, vocab: int, seed: int, seconds: float,
                  rate_rps: float | None = None) -> list[Request]:
    """Open loop: arrivals over ramp_s + seconds at `rate_rps`.  The
    seed orders the file's sizes and, separately, its gaps."""
    rate = float(rate_rps or traffic["arrivals"]["rate_rps"])
    total = float(traffic.get("ramp_s", 0.0)) + seconds
    n = max(1, int(round(rate * total)))
    pop = np.random.default_rng(traffic["population_seed"] + 1)
    order = np.random.default_rng([seed, 3])
    g = order.permutation(_gaps(pop, traffic["arrivals"], n, total))
    # the first request is due at 0, the last gap ends the schedule
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    plen, olen = sizes(traffic, n)
    pick = order.permutation(n)
    return _requests(traffic, vocab, seed, due, plen[pick], olen[pick])


def closed_pool(traffic: dict, vocab: int, seed: int) -> list[Request]:
    """Closed loop: the pool the clients draw from in turn, `pool_size`
    requests made of the file's `population` sizes over and over, each
    round in an order the seed gives."""
    n, k = int(traffic["pool_size"]), int(traffic["population"])
    plen, olen = sizes(traffic, k)
    order = np.random.default_rng([seed, 3])
    pick = np.concatenate([order.permutation(k)
                           for _ in range(-(-n // k))])[:n]
    return _requests(traffic, vocab, seed, np.zeros(n), plen[pick],
                     olen[pick])


class Clock:
    """perf_counter offsets from one origin, with the wall clock of the
    origin kept so spans (wall clock) can be set beside them."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.wall0 = time.time()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def wall(self, offset_s: float) -> float:
        return self.wall0 + offset_s


def run_open(reqs: list[Request], send, clock: Clock, stop_s: float,
             drain_s: float) -> None:
    """Offer `reqs` on their schedule.  `send(req, clock)` blocks until
    the request has finished and fills it in; each runs on a thread of
    its own, started when the request is due (a stream is consumed by a
    blocking read, so a request in flight needs a thread).  Requests due
    after stop_s are not sent.  Returns when all have finished or
    drain_s after stop_s."""
    threads = []
    for r in reqs:
        if r.due_s >= stop_s:
            break
        wait = r.due_s - clock.now()
        if wait > 0:
            time.sleep(wait)
        r.sent_s = clock.now()
        t = threading.Thread(target=guard, args=(send, r, clock),
                             name=f"req-{r.idx}", daemon=True)
        t.start()
        threads.append(t)
    deadline = stop_s + drain_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - clock.now()))
    for r, t in zip(reqs, threads):
        if t.is_alive() and r.error is None:
            r.error = "unfinished at the drain deadline"


def run_closed(pool: list[Request], send, clock: Clock, clients: int,
               stop_s: float, drain_s: float) -> list[Request]:
    """`clients` callers, each sending its next request from the pool
    when the last returns, until stop_s.  Returns the requests started,
    in starting order."""
    lock = threading.Lock()
    started: list[Request] = []

    def client():
        while clock.now() < stop_s:
            with lock:
                if len(started) >= len(pool):
                    return
                r = pool[len(started)]
                started.append(r)
            r.due_s = r.sent_s = clock.now()
            guard(send, r, clock)

    threads = [threading.Thread(target=client, name=f"client-{i}",
                                daemon=True) for i in range(clients)]
    for t in threads:
        t.start()
    deadline = stop_s + drain_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - clock.now()))
    return started


def guard(send, r: Request, clock: Clock) -> None:
    try:
        send(r, clock)
    except Exception as e:  # noqa: BLE001 - a failed request is counted
        r.error = f"{type(e).__name__}: {e}"[:300]
