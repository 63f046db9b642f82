"""Load generation: the benchmark's own copy.

Copied in substance from ``triton_client_tpu/utils/loadgen.py``
(``poisson_schedule``, ``run_open_loop``, ``co_percentile``,
``run_pool``), which is sound: arrivals are a pure function of the
seed (here: one fixed set of exponential gaps, permuted by the seed),
the dispatcher never waits for a response, and latency runs from the
SCHEDULED arrival, so a stall is charged to every request it delays.
What is added is what a benchmark needs and the original does not
record: how late the generator sent each request, and every response
handed to a checker. This module never imports jax.

A traffic file's ``loop`` names its kind. ``run.py`` looks up
``<loop>_requests`` (the input generator's draw as what the kind
sends), ``<loop>_loop`` (one measured window) and ``<loop>_sample``
(everything of the output check's sample once, in flight together as
the cell's traffic would have it) here by that name; all kinds take
the same arguments. A kind may also have ``<loop>_refused``: why a cell
may not run a mix as its file stands. ``open`` and ``closed`` send
single requests from a pool; ``sessions`` sends STREAMS: ordered
requests under one ``sequence_id``, each when the last one answered,
in whole rounds that each hold the whole pool.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import uuid

import numpy as np


def poisson_schedule(rate: float, duration_s: float, rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in seconds from window start: ``rate x
    duration_s`` exponential gaps, the SAME gaps for every seed (drawn
    from a fixed stream and scaled to fill the window), in an order the
    run's seed fixes. Every seed then offers the same amount of work
    and the same bursts, somewhere else in the window; with gaps drawn
    anew per seed the offered count alone moved by 2.5% from run to
    run, and the latencies with it."""
    n = max(1, int(round(rate * duration_s)))
    gaps = np.random.default_rng(20250927).exponential(1.0 / rate, n + 1)
    gaps *= duration_s / gaps.sum()
    return np.cumsum(rng.permutation(gaps))[:n]


def co_percentile(latencies_ms, scheduled: int, q: float) -> float:
    """Percentile over ALL scheduled requests: one that never completed
    counts as infinitely late (the coordinated-omission-safe form)."""
    lat = np.sort(np.asarray(latencies_ms, float))
    if scheduled <= 0:
        return float("nan")
    rank = int(np.ceil(q / 100.0 * scheduled)) - 1
    return float(lat[rank]) if rank < len(lat) else float("inf")


def split_items(request: dict) -> tuple[dict, int | None]:
    """A generated request -> (its input arrays, the items it states).
    A request of a stream may state its own item count under ``items``
    (a plain int beside the arrays); None means the traffic file's
    ``items_per_request``."""
    items = request.get("items")
    if isinstance(items, int):
        return {k: v for k, v in request.items() if k != "items"}, items
    return request, None


def flat(sample: list) -> list:
    """A sample's requests (or its responses) one after another,
    whether it is a list of them or a list of streams."""
    return [r for stream in sample for r in stream] if sample and isinstance(sample[0], list) else list(sample)


def first_request(sample: list) -> dict:
    """The input arrays of a sample's first request."""
    return split_items(flat(sample)[0])[0]


def stacked(sample: list, cfg: dict) -> dict:
    """A sample's requests as one batch (for a reference, or for the
    weights' calibration): requests that carry a batch axis (the
    configuration's ``request_batch_axis``) are concatenated, the
    others stacked."""
    requests = [split_items(r)[0] for r in flat(sample)]
    join = np.concatenate if cfg["request_batch_axis"] else np.stack
    return {k: join([r[k] for r in requests]) for k in requests[0]}


def first_items(batch: dict, n: int) -> dict:
    """The first ``n`` items of a stacked sample."""
    return {k: v[:n] for k, v in batch.items()}


def closed_requests(model: str, inputs: list[dict]) -> list:
    """The pool of an ``open`` or ``closed`` mix: one request a draw."""
    from triton_client_tpu.channel.base import InferRequest

    return [InferRequest(model, x) for x in inputs]


open_requests = closed_requests


def sessions_requests(model: str, streams: list[list[dict]]) -> list:
    """The pool of a ``sessions`` mix: each stream a list of
    ``(request, items)``, in the order it is sent."""
    from triton_client_tpu.channel.base import InferRequest

    return [[(InferRequest(model, inputs), items) for inputs, items in map(split_items, stream)]
            for stream in streams]


class Window:
    """What one measured window recorded."""

    scheduled = False  # an open loop's: latencies run from a fixed schedule

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.attempted = 0
        self.failed = 0
        self.items_done = 0
        self.latencies_ms: list[float] = []
        self.done_at_s: list[float] = []  # completion times from window start
        self.late_ms: list[float] = []
        self.errors: list[str] = []
        self.malformed = 0
        self.t_start = 0.0
        self.t_end = 0.0

    def span_s(self) -> float:
        """The length of the measured window."""
        return self.t_end - self.t_start

    def record(self, response, items: int, t_ref: float, check) -> None:
        """A completed request: its latency always counts; its items
        count as done only if it completed inside the window."""
        t_done = time.perf_counter()
        bad = check(response) if check is not None else None
        with self.lock:
            self.latencies_ms.append((t_done - t_ref) * 1e3)
            self.done_at_s.append(t_done - self.t_start)
            if t_done <= self.t_end:
                self.items_done += items
            if bad:
                self.malformed += 1
                if len(self.errors) < 5:
                    self.errors.append(bad)

    def end_to_end(self) -> dict:
        """What the window says end to end: items completed in it over
        its length and, where the arrivals were scheduled, the latency
        percentiles over all scheduled requests."""
        values = {"throughput": self.items_done / self.span_s()}
        if self.scheduled:
            values["latency_p50_ms"] = co_percentile(self.latencies_ms, self.attempted, 50)
            values["latency_p95_ms"] = co_percentile(self.latencies_ms, self.attempted, 95)
        return values

    def timeline(self) -> list[list[float]]:
        """Per second of the window: completions and their median
        latency in ms (for the builder's log: when did it stall?)."""
        sec = np.floor(np.asarray(self.done_at_s)).astype(int)
        lat = np.asarray(self.latencies_ms)
        return [[int(s), int((sec == s).sum()), round(float(np.median(lat[sec == s])), 1)]
                for s in sorted(set(sec.tolist()))]

    def fail(self, error) -> None:
        with self.lock:
            self.failed += 1
            if len(self.errors) < 5:
                self.errors.append(repr(error))


def open_loop(make_channel, channel, requests, traffic: dict, seconds: float,
              rng: np.random.Generator, check=None, rate=None, resolvers: int = 32) -> Window:
    """One open-loop window at a FIXED offered rate (the traffic file's
    ``rate_per_s``; ``rate`` is the builder's sweep). One thread walks
    the schedule and issues non-blocking calls; ``resolvers`` threads
    wait for the responses. ``requests`` is the cell's seeded pool; the
    arrivals draw from it in an order the seed fixes. The window's
    length is ``seconds``; a request due inside it that finishes after
    it counts at its true latency, and completes nothing in the window."""
    items_per_request = int(traffic["items_per_request"])
    offsets = poisson_schedule(float(rate or traffic["rate_per_s"]), seconds, rng)
    picks = rng.integers(0, len(requests), len(offsets))
    win = Window()
    win.scheduled = True
    win.attempted = len(offsets)
    pending: queue.Queue = queue.Queue()

    def resolve_loop() -> None:
        while True:
            item = pending.get()
            if item is None:
                return
            t_due, future = item
            try:
                response = future.result()
            except Exception as e:  # a failed request completes nothing
                win.fail(e)
                continue
            win.record(response, items_per_request, t_due, check)

    workers = [threading.Thread(target=resolve_loop, daemon=True) for _ in range(resolvers)]
    for w in workers:
        w.start()
    win.t_start = t0 = time.perf_counter()
    win.t_end = t0 + seconds
    for off, pick in zip(offsets, picks):
        due = t0 + float(off)
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        win.late_ms.append((time.perf_counter() - due) * 1e3)
        pending.put((due, channel.do_inference_async(requests[pick])))
    for _ in workers:
        pending.put(None)
    for w in workers:
        w.join()
    return win


def open_sample(make_channel, channel, requests, traffic: dict) -> list:
    """Every request once, issued at the cell's rate; the responses in
    the requests' order."""
    gap = 1.0 / float(traffic["rate_per_s"])
    futures = []
    for r in requests:
        futures.append(channel.do_inference_async(r))
        time.sleep(gap)
    return [f.result() for f in futures]


def closed_loop(make_channel, channel, requests, traffic: dict, seconds: float,
                rng: np.random.Generator, check=None, rate=None) -> Window:
    """``clients`` callers, each with a channel of its own, each sending
    its next request when the last one answered, for ``seconds``. The
    window runs from the start to the answer to the last request sent
    before ``seconds`` were over, and every request sent counts: all
    the work over all the time. (Cut at ``seconds`` sharp, a window of
    a hundred launches of some hundred frames each reads one of two
    values, a launch apart, by where its end falls between two
    answers; the time to the last answer is continuous.)"""
    items_per_request, clients = int(traffic["items_per_request"]), int(traffic["clients"])
    win = Window()
    orders = [rng.permutation(len(requests)) for _ in range(clients)]
    channels = [make_channel() for _ in range(clients)]
    start = threading.Barrier(clients + 1)
    deadline = [0.0]

    def client(channel, order) -> None:
        start.wait()
        i = 0
        while True:
            t_send = time.perf_counter()
            if t_send >= deadline[0]:
                return
            request = requests[order[i % len(order)]]
            i += 1
            with win.lock:
                win.attempted += 1
            try:
                response = channel.do_inference(request)
            except Exception as e:
                win.fail(e)
                continue
            win.record(response, items_per_request, t_send, check)

    threads = [threading.Thread(target=client, args=(c, o), daemon=True) for c, o in zip(channels, orders)]
    try:
        for t in threads:
            t.start()
        win.t_start = time.perf_counter()
        deadline[0] = win.t_start + seconds
        win.t_end = float("inf")  # until the last answer is in
        start.wait()
        for t in threads:
            t.join()
        win.t_end = win.t_start + max(win.done_at_s, default=seconds)
    finally:
        for c in channels:
            c.close()
    return win


def _in_lanes(lanes: int, pool: list, send) -> list:
    """``send`` of every member of ``pool`` once, ``lanes`` callers
    side by side; what it returned, in the pool's order."""
    answers = [None] * len(pool)

    def lane(k: int) -> None:
        for i in range(k, len(pool), lanes):
            answers[i] = send(pool[i])

    threads = [threading.Thread(target=lane, args=(k,)) for k in range(lanes)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return answers


def closed_sample(make_channel, channel, requests, traffic: dict) -> list:
    """Every request once, from the cell's ``clients`` callers side by
    side; the responses in the requests' order."""
    return _in_lanes(int(traffic["clients"]), requests, channel.do_inference)


def _send_stream(channel, stream, deadline=None, on_send=None, on_answer=None, on_error=None) -> list:
    """One stream under a fresh ``sequence_id``: its requests in order,
    each when the last one answered, ``sequence_start`` on the first
    and ``sequence_end`` on the last. Past ``deadline`` (a one-element
    list holding a ``perf_counter`` time) nothing more is sent, and a
    failed request ends the stream. The responses, in order."""
    sequence_id = f"bench-{uuid.uuid4().hex[:16]}"  # a stream opened is a new sequence id, in every round
    responses = []
    for k, (request, items) in enumerate(stream):
        t_send = time.perf_counter()
        if deadline is not None and t_send >= deadline[0]:
            break
        if on_send is not None:
            on_send()
        request = dataclasses.replace(request, sequence_id=sequence_id, sequence_start=k == 0,
                                      sequence_end=k == len(stream) - 1)
        try:
            response = channel.do_inference(request)
        except Exception as e:
            if on_error is None:
                raise
            on_error(e)
            break
        responses.append(response)
        if on_answer is not None:
            on_answer(response, items, t_send)
    return responses


def round_orders(rng: np.random.Generator, clients: int, n: int, rounds: int) -> list[list[int]]:
    """Who sends what: ``orders[c][r]`` is the pool stream caller ``c``
    opens in round ``r``. A round's ``clients`` streams are
    ``clients / n`` copies of the whole pool of ``n``, in an order
    drawn anew each round, so every round is the same work whatever the
    seed. ``clients`` is a multiple of ``n`` (``sessions_refused``)."""
    if clients % n:
        raise ValueError(f"{clients} callers over a pool of {n} streams: not a multiple")
    return np.stack([rng.permutation(np.tile(np.arange(n), clients // n)) for _ in range(rounds)]).T.tolist()


def sessions_refused(traffic: dict, pool: int) -> str | None:
    """Why a CELL may not run this ``sessions`` mix (``run.py`` asks
    before a measured run), or None."""
    if float(traffic.get("round_s", 0)) <= 0:
        return "a sessions mix states round_s, the nominal length of a round: --seconds is rounded to whole rounds"
    if int(traffic["clients"]) % pool:
        return (f"{traffic['clients']} callers over a pool of {pool} streams: not a multiple, "
                "so a round would not hold the whole pool and rounds would differ")
    return None


def sessions_loop(make_channel, channel, requests, traffic: dict, seconds: float,
                  rng: np.random.Generator, check=None, rate=None) -> Window:
    """``clients`` callers, each with a channel of its own, send ROUNDS:
    in each round a caller opens the stream ``round_orders`` gives it
    under a fresh ``sequence_id`` and sends its requests IN ORDER, each
    when the last one answered; then its next round (no barrier: the
    callers stay together because the server serves them together).

    The window is a WHOLE NUMBER of rounds, ``seconds`` over the
    traffic file's ``round_s`` (the nominal length of a round) rounded
    (a half rounds up), at least one, and ends as the closed loop's does, at the last
    answer: the same requests and items in every window of a cell, and
    ``throughput`` is that over the time the program took, continuous
    in its speed. (Cut at ``seconds``, with each caller drawing its own
    order, a window of 40 s held exactly two prompts a caller, 91% of
    its tokens: six seeds spread 7%, and a step 2% faster would have
    let a third burst of 32 prompts in, +45%: PERF.md section 2.) Such
    a window that is not over after three times its nominal length
    stops sending, and the loop raises.

    ``seconds`` under half a round (a warm-up, the round that starts a
    profiler) is ONE round cut by the deadline: nothing is sent after
    ``seconds``, and a stream that the deadline cuts is left open (the
    server reclaims it).

    A request completes the items it states, else the traffic file's
    ``items_per_request`` (a stream's first request can carry
    thousands, the later ones one each). A failed request ends its
    stream and counts as failed; the caller goes on to its next round."""
    items_per_request, clients = int(traffic["items_per_request"]), int(traffic["clients"])
    round_s = float(traffic["round_s"])
    whole = seconds >= round_s / 2
    rounds = max(1, int(seconds / round_s + 0.5))  # one where the deadline cuts it
    limit_s = 3 * rounds * round_s if whole else seconds
    win = Window()
    orders = round_orders(rng, clients, len(requests), rounds)
    channels = [make_channel() for _ in range(clients)]
    start = threading.Barrier(clients + 1)
    deadline = [0.0]

    def on_send() -> None:
        with win.lock:
            win.attempted += 1

    def on_answer(response, items, t_send) -> None:
        win.record(response, items_per_request if items is None else items, t_send, check)

    def client(channel, order) -> None:
        start.wait()
        for pick in order:
            _send_stream(channel, requests[pick], deadline, on_send, on_answer, win.fail)

    threads = [threading.Thread(target=client, args=(c, o), daemon=True) for c, o in zip(channels, orders)]
    try:
        for t in threads:
            t.start()
        win.t_start = time.perf_counter()
        deadline[0] = win.t_start + limit_s
        win.t_end = float("inf")  # until the last answer is in
        start.wait()
        for t in threads:
            t.join()
        win.t_end = win.t_start + max(win.done_at_s, default=seconds)
    finally:
        for c in channels:
            c.close()
    if whole and time.perf_counter() >= deadline[0]:
        raise RuntimeError(f"a window of {rounds} round(s), nominally {rounds * round_s:g} s, was not over after "
                           f"{limit_s:g} s: stopped sending at {win.attempted} requests")
    return win


def sessions_sample(make_channel, channel, requests, traffic: dict) -> list[list]:
    """Every stream once and whole, from the cell's ``clients`` callers
    side by side; each stream's responses in its own order, the streams
    in the pool's."""
    return _in_lanes(int(traffic["clients"]), requests, lambda stream: _send_stream(channel, stream))
