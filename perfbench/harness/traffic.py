"""Query traffic from a traffic file and ``--seed``: the one generator.

A traffic file (``perfbench/traffic/<name>.json``) is data only:

``loop``
    ``"open"``: requests are due on a schedule whatever the server does,
    and each is timed from when it was due (independent users);
    ``"closed"``: ``clients`` callers each send a request and wait for
    its answer before sending the next (callers that wait on replies).
``rate_per_s`` (open)
    Mean arrival rate.  The window holds ``round(rate * seconds)``
    requests, due at the times of one fixed Poisson sample path (drawn
    from ``gap_seed`` and scaled to fill the window exactly), each from
    a fixed origin of the pool (drawn from ``gap_seed`` too, each
    origin taking an equal share): every seed offers the same load
    from the same peers at the same times.
``clients`` (closed)
    Number of callers; caller ``c`` sits at origin ``c`` of the pool
    (modulo its size), so a round of requests splits evenly over the
    origins whatever the seed.
``server``
    ``max_batch``, ``batch_window_s``, ``max_queue`` of the batcher.
``drain_s``
    How long after the window closes a request may still be answered;
    one unanswered by then has failed.
``check_sample``
    How many answered requests the reference recomputes (see
    ``harness.check.sample``).

Origins come from the configuration's fixed pool.  ``--seed`` decides
each request's own seed, and so its query: nothing else.  Every seed
asks the same number of queries of the same sizes from the same
origins; the overlay and the origin pool belong to the configuration.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, List, Optional

import numpy as np

SEED_BITS = 62          # request seeds are uniform in [0, 2**62)


@dataclasses.dataclass
class Request:
    """One request of the window and what became of it."""

    origin: int
    seed: int
    due_s: float                    # from the window's open
    sent_s: float = math.nan
    done_s: float = math.nan        # math.nan: never answered
    result: object = None
    error: Optional[BaseException] = None

    @property
    def latency_s(self) -> float:
        """Due (open loop) or sent (closed loop) to answered; inf when
        it failed or never came."""
        if self.error is not None or math.isnan(self.done_s):
            return math.inf
        return self.done_s - self.due_s


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, int(seed)])


def open_schedule(traffic: dict, seed: int, seconds: float,
                  pool: List[int]) -> List[Request]:
    """The open loop's requests, in due order.

    The due times and the origin due at each are one sample path, the
    same for every seed: exponential gaps drawn from the traffic's
    ``gap_seed`` and scaled to fill the window, and the pool's origins,
    each taking an equal share of the times, in an order drawn from it
    too.  ``--seed`` decides each request's own seed."""
    count = max(1, round(float(traffic["rate_per_s"]) * seconds))
    path = np.random.default_rng(int(traffic["gap_seed"]))
    gaps = path.exponential(1.0, count)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    origins = path.permutation(np.resize(np.asarray(pool), len(due)))
    seeds = _rng(seed, 1).integers(0, 1 << SEED_BITS, len(due))
    return [Request(int(o), int(s), float(t))
            for o, s, t in zip(origins, seeds, due)]


class ClosedStream:
    """Each closed-loop client's endless stream of (origin, seed): the
    client's own origin, ``pool[client % len(pool)]``, and seeds drawn
    from ``--seed``."""

    def __init__(self, traffic: dict, seed: int, pool: List[int]):
        self.traffic, self.pool = traffic, pool
        self.rngs = [_rng(seed, 2 + c) for c in range(int(traffic["clients"]))]

    def next(self, client: int) -> tuple:
        o = self.pool[client % len(self.pool)]
        return int(o), int(self.rngs[client].integers(0, 1 << SEED_BITS))


def run_open(submit: Callable, requests: List[Request], t_open: float,
             seconds: float, drain_s: float) -> dict:
    """Send each request when it is due; wait for every answer.

    ``submit(req)`` returns a handle with ``result(timeout)``.  One
    thread sends, one collects in sending order (the server answers in
    that order), so an answer is stamped when it is ready and not when
    a later one is.  Returns how late the sender ran."""
    handles: list = [None] * len(requests)
    sent = threading.Semaphore(0)
    late: list = []

    def sender():
        for i, r in enumerate(requests):
            delay = t_open + r.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            r.sent_s = time.perf_counter() - t_open
            late.append(r.sent_s - r.due_s)
            try:
                handles[i] = submit(r)
            except Exception as e:        # shed at submit: the request
                r.error = e               # failed, the loop goes on
            sent.release()

    th = threading.Thread(target=sender, name="open-loop-sender")
    th.start()
    give_up = t_open + seconds + drain_s
    for i, r in enumerate(requests):
        sent.acquire()
        if handles[i] is not None:
            _collect(r, handles[i],
                     max(0.0, give_up - time.perf_counter()), t_open)
    th.join()
    return {"late_max_s": max(late, default=0.0),
            "late_mean_s": float(np.mean(late)) if late else 0.0}


def run_closed(submit: Callable, stream: ClosedStream, t_open: float,
               seconds: float, drain_s: float) -> List[Request]:
    """``clients`` callers, each sending its next request as soon as its
    last is answered, until the window closes; every request sent by
    then is waited for."""
    reqs: List[Request] = []
    lock = threading.Lock()
    close = t_open + seconds

    def client(c: int):
        while time.perf_counter() < close:
            o, s = stream.next(c)
            now = time.perf_counter() - t_open
            r = Request(o, s, now, sent_s=now)
            with lock:
                reqs.append(r)
            try:
                h = submit(r)
            except Exception as e:
                r.error = e
                continue
            _collect(r, h, max(0.0, close + drain_s - time.perf_counter()),
                     t_open)
            if r.error is not None and math.isnan(r.done_s):
                return                    # unanswered: the caller gives up

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"closed-loop-client-{c}")
               for c in range(int(stream.traffic["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(reqs, key=lambda r: r.sent_s)


def _collect(r: Request, handle, wait: float, t_open: float) -> None:
    try:
        r.result = handle.result(timeout=wait)
        r.done_s = time.perf_counter() - t_open
    except TimeoutError as e:             # never answered within the drain
        r.error = e
    except Exception as e:                # the server or engine failed it
        r.error = e
        r.done_s = time.perf_counter() - t_open


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    closest ranks, numpy's default; an infinite value (a failed request)
    counts as slower than every answered one."""
    a = np.sort(np.asarray(values, np.float64))
    if not len(a):
        return math.nan
    pos = (len(a) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(a) - 1)
    if math.isinf(a[hi]) or math.isinf(a[lo]):
        return float(a[hi]) if pos > lo else float(a[lo])
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))
