"""The one traffic generator: it reads a mix file (``traffic/<name>.json``)
and drives a ``repro.Session`` for the measured window.

A mix has an ``ingest`` section, a ``score`` section, or both (they then
run at once: score clients on threads of their own, ingest on the calling
thread).

* ``ingest`` -- ``{"loop": "closed", "batch": B}``: one caller feeds
  batches of B rows of the population, cycled in order, as fast as
  ``Session.ingest`` returns.
* ``score`` -- ``{"loop": "open", "clients": C, "rows_mean": M,
  "rows_max": R, "rate_of_knee": f}``: C client threads, open-loop
  arrivals with exponential gaps, requests whose row counts follow the
  geometric law of mean M, capped at R, rows drawn from the population;
  together they offer f times the configuration's ``knee_rows_per_s``
  rows per second.  Every seed offers the same work: each client's sizes
  and gaps are the quantiles of those laws, and the seed only shuffles
  them and draws the rows.  The schedule is made before the window
  opens.  Each request is timed
  from its *due* time (not from when its client got round to sending it)
  to the moment its client sees the result of its last row; how late the
  clients sent is reported apart.

The open-loop idea follows ``repro.serve.loadgen``; the clock is the
difference (latency there runs from submit).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

import numpy as np

clock = time.perf_counter
RESULT_WAIT_S = 60.0     # how long past the window an answer may come


class Cycle:
    """The population as an endless stream, in order."""

    def __init__(self, x: np.ndarray, start: int = 0):
        self.x, self.pos, self.fed = x, start % x.shape[0], 0

    def take(self, b: int) -> np.ndarray:
        n = self.x.shape[0]
        if self.pos + b <= n:
            out = self.x[self.pos:self.pos + b]
        else:
            out = np.concatenate([self.x[self.pos:],
                                  self.x[:self.pos + b - n]])
        self.pos = (self.pos + b) % n
        self.fed += b
        return out


def annotate(name: str):
    """A host annotation in the profiler's trace (no-op when untraced)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------ ingest
def ingest_closed(session, stream: Cycle, batch: int, seconds: float,
                  t_open: float) -> dict:
    calls = []
    t_end = t_open + seconds
    while True:
        t0 = clock()
        if t0 >= t_end:
            break
        rows = stream.take(batch)
        with annotate("bench.ingest"):
            session.ingest(rows)
        calls.append((t0, clock(), rows.shape[0]))
    return {"calls": calls, "points": sum(c[2] for c in calls),
            "elapsed": (calls[-1][1] - t_open) if calls else 0.0}


# ------------------------------------------------------------ score
def _quantiles(m: int) -> np.ndarray:
    return (np.arange(m) + 0.5) / m


def request_sizes(m: int, mean: float, cap: int) -> np.ndarray:
    """The m quantiles of the geometric law on 1, 2, ... of this mean,
    capped."""
    k = np.ceil(np.log1p(-_quantiles(m)) / np.log1p(-1.0 / mean))
    return np.clip(k, 1, cap).astype(np.int64)


def score_schedule(sec: dict, rate: float, seconds: float, n_pop: int,
                   rng: np.random.Generator) -> list[list[dict]]:
    """Per client, its requests in order: due offset, row ids.  A client's
    m gaps are the exponential law's quantiles, scaled so that its last
    request falls due at seconds * m / (m + 1)."""
    clients = int(sec["clients"])
    mean, cap = float(sec["rows_mean"]), int(sec["rows_max"])
    per_client = rate * seconds / clients
    m = max(1, round(per_client / mean))
    m = max(1, round(per_client / request_sizes(m, mean, cap).mean()))
    sizes = request_sizes(m, mean, cap)
    gaps = -np.log1p(-_quantiles(m))
    gaps *= seconds * m / (m + 1) / gaps.sum()
    out = []
    for _ in range(clients):
        due = np.cumsum(rng.permutation(gaps))
        rows = rng.permutation(sizes)
        ids = rng.integers(0, n_pop, size=int(rows.sum()))
        splits = np.split(ids, np.cumsum(rows)[:-1])
        out.append([{"due": float(d), "ids": s} for d, s in zip(due, splits)])
    return out


def _wait_all(tickets, start: int, timeout: float) -> int:
    """Index of the first ticket still pending after waiting up to
    ``timeout`` (len(tickets) when all are resolved)."""
    deadline = clock() + max(timeout, 0.0)
    i = start
    while i < len(tickets):
        if not tickets[i].done():
            left = deadline - clock()
            if left <= 0:
                return i
            try:
                tickets[i].result(left)
            except TimeoutError:
                return i
            except Exception:        # a worker error: the row is answered
                pass
        i += 1
    return i


def answers(tickets) -> list:
    """Per row: (center, distance, score, flag), "shed", "error" or None
    (never answered).  Taken as a request completes, so that the window
    holds no tickets once they are answered."""
    out = []
    for tk in tickets:
        if not tk.done():
            out.append(None)
            continue
        try:
            v = tk.result(0)
        except Exception:
            out.append("error")
            continue
        out.append((v.center, v.distance, v.outlier_score, v.is_outlier)
                   if hasattr(v, "distance") else "shed")
    return out


def _client(session, x, plan, t_open: float, seconds: float, out: list):
    pending: deque = deque()         # [request index, tickets, next ticket]
    i = 0
    while i < len(plan) or pending:
        now = clock()
        if i < len(plan) and t_open + plan[i]["due"] <= now:
            req = plan[i]
            with annotate("bench.score.submit"):
                tickets = session.submit_stream(x[req["ids"]])
            out[i] = {"submit": clock(), "done": None, "answers": None}
            pending.append([i, tickets, 0])
            i += 1
            continue
        if pending:
            j, tickets, k = pending[0]
            limit = (t_open + plan[i]["due"] if i < len(plan)
                     else t_open + seconds + RESULT_WAIT_S)
            k = _wait_all(tickets, k, limit - clock())
            pending[0][2] = k
            if k == len(tickets):
                out[j]["done"] = clock()
                out[j]["answers"] = answers(tickets)
                pending.popleft()
            elif i >= len(plan) and clock() >= limit:
                break                # never answered: left as not done
        else:
            time.sleep(max(0.0, t_open + plan[i]["due"] - clock()))
    for j, tickets, _ in pending:
        out[j]["answers"] = answers(tickets)


def score_open(session, x, plans, t_open: float, seconds: float) -> dict:
    outs = [[None] * len(p) for p in plans]
    threads = [threading.Thread(target=_client, name=f"bench-client-{c}",
                                args=(session, x, plans[c], t_open, seconds,
                                      outs[c]))
               for c in range(len(plans))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    requests = []
    for plan, out in zip(plans, outs):
        for req, got in zip(plan, out):
            requests.append({"due": t_open + req["due"], "ids": req["ids"],
                             **(got or {"submit": None, "done": None,
                                        "answers": None})})
    return {"requests": requests}


# ------------------------------------------------------------ window
def run_window(session, stream: Cycle, x, mix: dict, seconds: float,
               plans: Optional[list], on_open: Callable[[float], None]):
    """Drive the mix for ``seconds``; returns the raw samples."""
    result: dict = {}
    t_open = clock()
    on_open(t_open)
    score_thread = None
    if mix.get("score"):
        box: dict = {}
        score_thread = threading.Thread(
            target=lambda: box.update(score_open(session, x, plans, t_open,
                                                 seconds)),
            name="bench-score")
        score_thread.start()
    if mix.get("ingest"):
        result["ingest"] = ingest_closed(session, stream,
                                         int(mix["ingest"]["batch"]),
                                         seconds, t_open)
    if score_thread is not None:
        score_thread.join()
        result.update(box)
    result["t_open"] = t_open
    result["t_close"] = max(t_open + seconds, clock() if mix.get("ingest")
                            else t_open + seconds)
    return result
