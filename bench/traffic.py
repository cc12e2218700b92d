"""The one traffic generator: it reads a mix's parameters and drives a system.

A mix (``traffic/<name>.json``) sets:

* ``op``: ``search`` (answer queries from the pool) or ``build`` (index the
  whole corpus again, the next seed each time);
* ``loop``: ``closed`` (one client; its next request leaves when the last one
  returned) or ``open`` (requests arrive on a schedule whatever the system
  does);
* for ``search``: ``request_queries`` per request and ``topk``;
* for ``open``: ``arrivals`` (``poisson``), ``rate_per_s``, and ``max_batch``:
  a real-time batcher drains the queued arrivals, at most ``max_batch`` of
  them, and pads the micro-batch to ``max_batch`` rows so that its shape
  never changes (the policy of ``replay_query_streams`` in
  ``repro/vdms/workload.py``, here on the real clock).

Every seed gets the same amount of work: a closed loop cycles through the
pool in order, and an open loop's gaps are the same set of exponential
quantiles for every seed, in an order drawn from the seed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

OPS = ("search", "build")
LOOPS = ("closed", "open")


def span(name: str) -> TraceAnnotation:
    """A host span the trace reduction finds by its ``bench/`` prefix."""
    return TraceAnnotation("bench/" + name)


def validate(mix: dict) -> dict:
    """Refuse a mix the generator cannot run, before any set-up."""
    op, loop = mix.get("op"), mix.get("loop")
    if op not in OPS or loop not in LOOPS:
        raise ValueError(f"traffic needs op in {OPS} and loop in {LOOPS}, got {op!r}, {loop!r}")
    if op == "build" and loop != "closed":
        raise ValueError("builds run back to back: loop must be 'closed'")
    if op == "search":
        for key in ("request_queries", "topk"):
            if int(mix.get(key, 0)) < 1:
                raise ValueError(f"search traffic needs a positive {key!r}")
    if loop == "open":
        if mix.get("arrivals") != "poisson":
            raise ValueError("open traffic supports arrivals 'poisson'")
        if float(mix.get("rate_per_s", 0)) <= 0 or int(mix.get("max_batch", 0)) < 1:
            raise ValueError("open traffic needs a positive 'rate_per_s' and 'max_batch'")
        if int(mix["request_queries"]) != 1:
            raise ValueError("open traffic sends single-query requests")
    return mix


def poisson_arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Arrival times of a Poisson stream at ``rate`` over ``seconds``.

    The gaps are the ``n = rate * seconds`` exponential quantiles
    ``-ln(1 - (i + 1/2) / n) / rate``, permuted by the seed: every seed
    offers the same requests in the same total time, in another order.
    (``poisson_arrivals`` in ``repro/vdms/workload.py`` draws i.i.d. gaps.)
    """
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))


@dataclasses.dataclass
class Log:
    """What a window did, on the window's clock (seconds from its start)."""

    calls: list = dataclasses.field(default_factory=list)  # (start, end, items)
    scheduled: list = dataclasses.field(default_factory=list)  # per request
    done: list = dataclasses.field(default_factory=list)  # per request
    rows: list = dataclasses.field(default_factory=list)  # pool rows answered, per call
    answers: list = dataclasses.field(default_factory=list)  # their ids, per call
    late_s: list = dataclasses.field(default_factory=list)  # open loop: dispatch - arrival
    kept: object = None  # build: the one build kept for the check
    clusters: list = dataclasses.field(default_factory=list)  # build: each build's clusters
    builds: int = 0

    @property
    def end(self) -> float:
        return max(c[1] for c in self.calls) if self.calls else 0.0

    def items(self) -> int:
        return int(sum(c[2] for c in self.calls))


def _closed_search(searcher, pool, mix, seconds, log, clock, t0, on_done):
    b, topk, n_pool = int(mix["request_queries"]), int(mix["topk"]), pool.shape[0]
    r = 0
    while clock() - t0 < seconds:
        with span("batch_assembly"):
            rows = (r * b + np.arange(b)) % n_pool
            q = pool[rows]
        start = clock() - t0
        with span("search_call"):
            ids = searcher.search(q, topk)
        end = clock() - t0
        log.calls.append((start, end, b))
        log.scheduled.append(start)
        log.done.append(end)
        log.rows.append(rows)
        log.answers.append(ids)
        on_done(end)
        r += 1


def _open_search(searcher, pool, mix, seconds, log, clock, t0, seed, on_done):
    topk, width = int(mix["topk"]), int(mix["max_batch"])
    arrivals = poisson_arrivals(float(mix["rate_per_s"]), seconds, seed)
    n, n_pool = arrivals.size, pool.shape[0]
    done = np.empty(n)
    i = 0
    while i < n:
        now = clock() - t0
        if arrivals[i] > now:
            with span("generator_wait"):
                time.sleep(arrivals[i] - now)
            continue
        with span("batch_assembly"):
            j = i + int(np.searchsorted(arrivals[i : i + width], now, side="right"))
            rows = np.arange(i, j) % n_pool
            q = pool[rows]
            if q.shape[0] < width:  # pad to the one compiled shape
                q = np.concatenate([q, np.repeat(q[:1], width - q.shape[0], axis=0)])
        start = clock() - t0
        with span("search_call"):
            ids = searcher.search(q, topk)
        end = clock() - t0
        log.calls.append((start, end, j - i))
        log.late_s.extend(start - arrivals[i:j])
        done[i:j] = end
        log.rows.append(rows)
        log.answers.append(ids[: j - i])
        on_done(end)
        i = j
    log.scheduled = arrivals.tolist()
    log.done = done.tolist()


def _closed_build(system, dataset, seconds, log, clock, t0, seed, on_done):
    rng = np.random.default_rng(seed)
    b = 0
    while clock() - t0 < seconds:
        start = clock() - t0
        with span("build"):
            built = system.build(dataset, seed=b + 1)
        end = clock() - t0
        log.calls.append((start, end, dataset.n))
        log.scheduled.append(start)
        log.done.append(end)
        log.clusters.append(system.clusters(built))  # device arrays, read after the window
        if rng.integers(0, b + 1) == 0:  # reservoir: each build kept with equal chance
            log.kept = built
        del built
        on_done(end)
        b += 1
    log.builds = b


def drive(mix, *, system, searcher, dataset, pool, seconds, seed, on_done=lambda elapsed: None,
          clock=time.perf_counter) -> Log:
    """Run one measured window of ``mix`` and return its log. ``on_done`` is
    called with the window's elapsed seconds after each request returns."""
    log = Log()
    t0 = clock()
    with span("window"):
        if mix["op"] == "build":
            _closed_build(system, dataset, seconds, log, clock, t0, seed, on_done)
        elif mix["loop"] == "closed":
            _closed_search(searcher, pool, mix, seconds, log, clock, t0, on_done)
        else:
            _open_search(searcher, pool, mix, seconds, log, clock, t0, seed, on_done)
    return log


def warm(mix, *, system, searcher, dataset, pool):
    """Run each shape the window will use once, outside the window."""
    if mix["op"] == "build":
        return
    width = int(mix["request_queries"] if mix["loop"] == "closed" else mix["max_batch"])
    searcher.search(pool[np.arange(width) % pool.shape[0]], int(mix["topk"]))
