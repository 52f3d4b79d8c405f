"""The closed loop: one client per batch slot, each sending its next request
when the step that finished its last one returns, and the bookkeeping of
every token's arrival.

Tokens come back at the return of the ``step_decode`` call that appended
them, so every time here is a step's return on the host clock.  A request's
time to first token runs from its send to the return of the step that
appended its first token; its inter-token gaps run between the returns of
consecutive steps that appended its tokens, so a step that stalls on a
neighbour's prefill shows in every gap it holds up.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, ContextManager

import numpy as np


@dataclasses.dataclass
class Step:
    kind: str                   # "admit": it admitted requests; else "decode"
    t0: float
    t1: float
    admitted: list[int]         # prompt lengths of the requests it admitted
    contexts: list[int]         # per decode row: tokens in context, fed one
                                # included


@dataclasses.dataclass
class Sent:
    req: object                 # the engine's Request
    sent_at: float
    first_at: float | None = None
    last_at: float | None = None


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    steps: list[Step]
    ttft_s: list[float]          # first tokens in the window, and waits so far
    gaps_s: list[float]          # inter-token gaps that ended in the window
    attempted: int               # requests in flight at the open or sent in it
    finished: list[Sent]         # requests finished in the window
    in_flight: list[Sent]        # requests holding tokens at the close
    traced_from: int | None = None   # index of the first traced step

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    @property
    def tokens(self) -> int:
        return sum(len(s.contexts) for s in self.steps)

    @property
    def traced_steps(self) -> list[Step]:
        return [] if self.traced_from is None else self.steps[self.traced_from:]


def no_span(_name: str) -> ContextManager:
    return contextlib.nullcontext()


class ClosedLoop:
    """Drives ``engine.step_decode`` with ``n_clients`` closed-loop clients
    whose requests come from ``stream``."""

    def __init__(self, engine, stream, n_clients: int,
                 span: Callable[[str], ContextManager] = no_span,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine, self.stream, self.n_clients = engine, stream, n_clients
        self.span, self.clock = span, clock
        self.live: list[Sent] = []       # sent and not finished
        self.done: list[Sent] = []
        self.ttft: list[tuple[float, float]] = []   # (return time, seconds)
        self.gaps: list[tuple[float, float]] = []

    def send(self, now: float) -> Sent:
        prompt, n_out = self.stream.next()
        s = Sent(self.engine.submit(prompt, n_out), now)
        self.live.append(s)
        return s

    def step(self) -> Step:
        eng = self.engine
        queued = list(eng.queue)
        kind = "admit" if queued and len(eng.active) < eng.max_batch \
            else "decode"
        before = {id(s): len(s.req.generated) for s in self.live}
        t0 = self.clock()
        with self.span(f"bench.step.{kind}"):
            eng.step_decode()
        t1 = self.clock()
        still = {id(r) for r in eng.queue}
        admitted = [len(r.prompt) for r in queued if id(r) not in still]
        contexts = []
        for s in list(self.live):
            g0, g1 = before[id(s)], len(s.req.generated)
            if g1 == g0:
                continue
            if g1 != g0 + 1:
                raise RuntimeError(f"request {s.req.req_id} got {g1 - g0} "
                                   "tokens in one step")
            contexts.append(len(s.req.prompt) + g0)
            if g0 == 0:
                s.first_at = t1
                self.ttft.append((t1, t1 - s.sent_at))
            else:
                self.gaps.append((t1, t1 - s.last_at))
            s.last_at = t1
            if s.req.done:
                self.live.remove(s)
                self.done.append(s)
                self.send(t1)
        return Step(kind, t0, t1, admitted, contexts)

    def fill(self) -> float:
        """Send every client's first request and step until each has been
        admitted and holds its first token; return when that step ended."""
        now = self.clock()
        first = [self.send(now) for _ in range(self.n_clients)]
        while any(s.first_at is None for s in first):
            t1 = self.step().t1
        return t1

    def run(self, t_open: float, seconds: float, trace_s: float = 0.0,
            start_trace: Callable[[], None] | None = None) -> Window:
        """Step until ``seconds`` have passed since ``t_open`` (the return
        of the fill); the window closes at the return of its last step.

        With ``start_trace``, it is called once, between two steps, when
        about ``trace_s`` seconds of the window are left, so that the trace
        holds the window's last steps whole; the caller stops it after the
        close."""
        attempted_before = len(self.live)
        n_done, n_ttft, n_gaps = len(self.done), len(self.ttft), len(self.gaps)
        sent_before = {id(s) for s in self.live}
        steps: list[Step] = []
        traced_from = None
        while not steps or steps[-1].t1 - t_open < seconds:
            if start_trace is not None and traced_from is None and (
                    (steps[-1].t1 if steps else t_open) - t_open
                    >= seconds - trace_s):
                start_trace()
                traced_from = len(steps)
            steps.append(self.step())
        t_close = steps[-1].t1
        last_start = steps[-1].t0
        waiting = [t_close - s.sent_at for s in self.live
                   if s.first_at is None and s.sent_at < last_start]
        sent_in = [s for s in self.live + self.done[n_done:]
                   if id(s) not in sent_before]
        return Window(
            t_open, t_close, steps,
            ttft_s=[v for _, v in self.ttft[n_ttft:]] + waiting,
            gaps_s=[v for _, v in self.gaps[n_gaps:]],
            attempted=attempted_before + len(sent_in)
            - sum(1 for s in sent_in if s.sent_at == t_close),
            finished=self.done[n_done:],
            in_flight=[s for s in self.live if s.req.generated],
            traced_from=traced_from)


TAIL_SHARE = 0.05   # the slowest share of the gaps that the tail averages


def tail_mean(xs: list[float], share: float) -> float:
    """Mean of the slowest ``share`` of ``xs``, the sample at the edge
    weighted by the part of it that falls inside.  Unlike a percentile it
    does not jump when one more sample moves its edge between two clusters
    of equal stalls."""
    s = sorted(xs, reverse=True)
    m = share * len(s)
    full = min(int(m), len(s) - 1)
    frac = m - full
    return (sum(s[:full]) + frac * s[full]) / m


def end_to_end(w: Window) -> dict[str, float]:
    """The window's end-to-end readings, each None where it has no sample."""
    def pct(xs, q, scale):
        return float(np.percentile(xs, q)) * scale if xs else None
    return {
        "out_tok_per_s": w.tokens / w.seconds,
        "ttft_p50_s": pct(w.ttft_s, 50, 1.0),
        "itl_p50_ms": pct(w.gaps_s, 50, 1e3),
        "itl_worst5pct_ms": (1e3 * tail_mean(w.gaps_s, TAIL_SHARE)
                             if w.gaps_s else None),
    }
