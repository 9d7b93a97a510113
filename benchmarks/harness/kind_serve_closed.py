"""Traffic kind ``serve_closed``: N closed-loop clients against the
generation server, each sending its next request when its last one ends.

Prompt and output lengths are fixed lists in the traffic file, walked in the
order written; the seed draws the token ids, so every seed offers the same
work in the same order.
Clients are callbacks, not threads: a client's next request is submitted
from the ``on_token`` call that delivers its last token, so the load comes
from one process with no threads of its own.  Times are the client's side:
``submit_async`` -> first ``on_token`` -> each later ``on_token``.

The window opens once every client has had its first token.  The server
delivers tokens a scheduler turn at a time, thirty-two at once after a decode,
so the window is cut where turns begin (the first token delivered after
``engine.prefill`` or ``engine.decode`` returns): it opens at the first turn's delivery after set-up
and closes at the first turn's delivery ``--seconds`` or more later, as a
training window runs from fence to fence.  (Cut on the clock alone, a window
held 209 or 210 decode turns by chance, and the rate read 155.42 or 156.22.)
Tokens, first tokens and gaps are counted by when they were delivered.  After the window the server is stopped, its memory freed, and a
sample of the requests it finished, the longest among them, is run through
the plain reference: the widest gap by which a served token's logit lies
below the reference's best decides ``correct``.
"""
import gc
import sys
import threading
import time

import numpy as np

from . import correct, runtime, stats, tracing, weights


class Request:
    __slots__ = ("index", "client", "prompt", "max_new", "t_submit",
                 "times", "tokens", "fut", "error")

    def __init__(self, index, client, prompt, max_new):
        self.index, self.client = index, client
        self.prompt, self.max_new = prompt, max_new
        self.t_submit = None
        self.times, self.tokens = [], []
        self.fut = self.error = None

    @property
    def finished(self):
        return len(self.tokens) >= self.max_new


def plan(traffic, seed, vocab):
    """An endless deterministic sequence of (prompt ids, output length):
    the file's length lists walked round in the order written, the same for
    every seed; the seed draws the token ids.  (A window holds some forty of
    the sixty-four requests, so a seed that permuted the lists changed which
    forty: ``ttft_p50_ms`` read 69, 69 and 82 ms on three seeds.)"""
    p_lens = np.asarray(traffic["prompt_lens"])
    o_lens = np.asarray(traffic["output_lens"])

    def make(index):
        n = int(p_lens[index % len(p_lens)])
        ids = weights.host_rng(seed, 1000 + index).integers(
            0, vocab, size=n, dtype=np.int32)
        return ids, int(o_lens[index % len(o_lens)])
    return make


class ClosedLoop:
    def __init__(self, server, make_request, clients):
        self.server, self.make, self.clients = server, make_request, clients
        self.requests = []
        self.lock = threading.Lock()
        self.closed = False
        self.now = time.perf_counter
        self.turns = []            # when each turn's first token came
        self._new_turn = False

    def submit(self, client):
        import jax

        with self.lock:
            if self.closed:
                return
            index = len(self.requests)
            prompt, max_new = self.make(index)
            req = Request(index, client, prompt, max_new)
            self.requests.append(req)
        with jax.profiler.TraceAnnotation("bench.client_submit"):
            req.t_submit = self.now()
            try:
                req.fut = self.server.submit_async(
                    prompt, max_new_tokens=max_new,
                    on_token=lambda tok, r=req: self._on_token(r, tok))
            except Exception as e:        # refused: counts as failed
                req.error = repr(e)

    def _on_token(self, req, tok):
        at = self.now()
        if self._new_turn:                     # scheduler thread only
            self._new_turn = False
            self.turns.append(at)
        req.times.append(at)
        req.tokens.append(int(tok))
        if req.finished:
            self.submit(req.client)

    def start(self):
        for c in range(self.clients):
            self.submit(c)

    def engine_returned(self):
        """The engine has new logits: the next token delivered opens a
        turn."""
        self._new_turn = True

    def turn_at_or_after(self, t):
        """When the first turn at or after ``t`` began to deliver, or None
        while none has."""
        return next((at for at in list(self.turns) if at >= t), None)

    def all_started(self):
        with self.lock:
            first = self.requests[:self.clients]
        return all(r.times or r.error for r in first)


def wrap_engine(engine, calls, returned):
    """Spans around the engine's two device entries, from the benchmark's
    side: what each call processed and how long the host waited on it;
    ``returned()`` tells the clients' side that a turn's tokens follow."""
    import jax

    prefill, decode = engine.prefill, engine.decode

    def timed_prefill(prompt, table):
        with jax.profiler.TraceAnnotation("engine.prefill"):
            t0 = time.perf_counter()
            out = prefill(prompt, table)
            calls["prefill"].append((t0, time.perf_counter(),
                                     int(prompt.shape[0])))
        returned()
        return out

    def timed_decode(seqs):
        lens = [int(s.length) + 1 for s in seqs]
        with jax.profiler.TraceAnnotation("engine.decode"):
            t0 = time.perf_counter()
            out = decode(seqs)
            calls["decode"].append((t0, time.perf_counter(), lens))
        returned()
        return out

    engine.prefill, engine.decode = timed_prefill, timed_decode


def measure(requests, t_open, t_close):
    """Client-side numbers of the window from the requests' time stamps."""
    tokens = 0
    gaps, ttfts = [], []
    attempted = failed = 0
    for r in requests:
        tokens += sum(1 for t in r.times if t_open <= t < t_close)
        gaps.extend(gap for gap, at in zip(stats.token_gaps(r.times),
                                           r.times[1:])
                    if t_open <= at < t_close)
        if r.t_submit is not None and t_open <= r.t_submit < t_close:
            attempted += 1
            if r.times:
                ttfts.append(r.times[0] - r.t_submit)
            else:
                failed += 1
    return {"tokens": tokens, "gaps": gaps, "ttfts": ttfts,
            "attempted": attempted, "failed": failed}


def pick_sample(requests, t_open, t_close, seed, n):
    """``n`` of the requests finished inside the window, drawn from the
    seed, the longest always among them."""
    done = [r for r in requests if r.finished and r.times
            and t_open <= r.times[-1] <= t_close]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt.size + len(r.tokens),
                                       -r.index))
    rest = [r for r in done if r is not longest]
    order = weights.host_rng(seed, 3).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(0, n - 1)]]


def _wait_for(poll, limit_s=60.0):
    """``poll()`` until it gives something, a millisecond at a time."""
    deadline = time.perf_counter() + limit_s
    while True:
        got = poll()
        if got is not None:
            return got
        if time.perf_counter() > deadline:
            sys.exit("benchmark: the server delivered no token for %ds"
                     % limit_s)
        time.sleep(0.001)


def _ms(seconds):
    return None if seconds is None else 1e3 * seconds


def run(cell, args, devices, t_start):
    import jax

    family = cell.family
    traffic, config = cell.traffic, cell.config
    if not args.readings:
        correct.refuse_unset(traffic["limits"])
    compiles = runtime.CompileCounter()
    server, params = family.make_server(config, traffic, args.seed, devices)
    calls = {"prefill": [], "decode": []}
    loop = ClosedLoop(server, plan(traffic, args.seed,
                                   config["model"]["vocab_size"]),
                      traffic["clients"])
    wrap_engine(server.engine, calls, loop.engine_returned)
    loop.start()
    t_wait = time.perf_counter()
    while not loop.all_started():
        if time.perf_counter() - t_wait > 600:
            sys.exit("benchmark: clients never all got a first token")
        time.sleep(0.01)
    counters0 = family.program_counters()
    runtime.quiet_host()
    compiles_before = compiles.count
    seconds = args.seconds
    tracer = None
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        tracer = tracing.Tracer(devices)
        tracer.start()
    t_ready = time.perf_counter()
    t_open = _wait_for(lambda: loop.turn_at_or_after(t_ready))
    setup_s = t_open - t_start
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = _wait_for(lambda: loop.turn_at_or_after(t_open + seconds))
    loop.closed = True
    trace = tracer.stop() if tracer else None
    runtime.unquiet_host()
    recompiles = compiles.count - compiles_before
    counters1 = family.program_counters()

    # a first token that is late is late, not missing: wait for it
    with loop.lock:
        requests = list(loop.requests)
    waiting = [r for r in requests if r.t_submit is not None
               and t_open <= r.t_submit < t_close and not r.error]
    t_wait = time.perf_counter()
    while any(not r.times and not r.fut.done for r in waiting):
        if time.perf_counter() - t_wait > 60:
            break
        time.sleep(0.01)
    server.close(timeout=0.05)
    win = measure(requests, t_open, t_close)
    win.update(t_open=t_open, t_close=t_close, seconds=t_close - t_open)
    device = runtime.device_record(devices)
    max_slots = traffic["server"]["max_slots"]
    del server, params, loop
    gc.collect()
    jax.clear_caches()

    sample = pick_sample(requests, t_open, t_close, args.seed,
                         traffic["sample_requests"])
    gaps, control_gaps = family.serve_reference_gaps(
        config, args.seed, [(r.prompt, r.tokens) for r in sample],
        control=args.readings == "control")
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    rows = [("served_logit_gap", widest, traffic["limits"]["served_gap"]),
            ("sampled_tokens", float(sum(len(g) for g in gaps)), None),
            ("failed_requests", float(win["failed"]), 0.0),
            ("compiles_in_window", float(recompiles), 0.0)]
    if control_gaps:
        rows.append(("control_logit_gap",
                     max(float(g.max()) for g in control_gaps), None))
    rate = win["tokens"] / win["seconds"]
    ctx = {"cell": cell, "window": win, "trace": trace, "rate": rate,
           "recompiles": recompiles, "devices": devices, "calls": calls,
           "max_slots": max_slots,
           "counters": {k: counters1[k] - counters0.get(k, 0)
                        for k in counters1}}
    runtime.say("window %.3fs tokens %d requests submitted %d first tokens "
                "%d gaps %d finished-in-window sample %d setup %.2fs"
                % (win["seconds"], win["tokens"], win["attempted"],
                   len(win["ttfts"]), len(win["gaps"]), len(sample),
                   setup_s))
    runtime.say("ttft ms of the window's requests, in order of submission: "
                + " ".join("%.0f" % (1e3 * t) for t in win["ttfts"]))
    if win["gaps"]:
        runtime.say("itl ms p50 %.2f p95 %.2f p99 %.2f; ttft ms p50 %s"
                    % (1e3 * stats.median(win["gaps"]),
                       1e3 * stats.percentile(win["gaps"], 95),
                       1e3 * stats.percentile(win["gaps"], 99),
                       win["ttfts"] and "%.2f" % (
                           1e3 * stats.median(win["ttfts"]))))
    correct.print_rows(rows, sys.stderr)
    return tracing.result(
        cell, args, ctx, device,
        {"gen_tokens_per_s": rate,
         "ttft_p50_ms": _ms(stats.median(win["ttfts"])),
         "itl_p95_ms": _ms(stats.percentile(win["gaps"], 95)),
         "setup_s": setup_s}, rows,
        attempted=win["attempted"], failed=win["failed"])
