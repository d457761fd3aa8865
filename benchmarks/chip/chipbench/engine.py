"""The system under test with the benchmark's spans around its layers, and
the open-loop client that drives it.

``SpanEngine`` is the program's ``ServeEngine`` with host timestamps and a
``jax.profiler.TraceAnnotation`` around each call into the model layer
(``_prefill_exec``, ``_decode_exec``). It changes nothing the engine does.
``Client`` submits each request of a schedule when it is due, whether or not
earlier ones have finished, and records when every token arrives.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve import ServeEngine


def now_ns() -> int:
    return time.monotonic_ns()


@dataclasses.dataclass
class Span:
    t0: int
    t1: int
    info: object  # prefill: (prompt_len, submit_ns); decode: positions


class SpanEngine(ServeEngine):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.prefill_spans: list = []
        self.decode_spans: list = []

    def _prefill_exec(self, req, slot):
        t0 = now_ns()
        with TraceAnnotation("bench.prefill"):
            first = super()._prefill_exec(req, slot)
        self.prefill_spans.append(
            Span(t0, now_ns(), (len(req.prompt), req.submit_ns)))
        return first

    def _decode_exec(self, live):
        positions = [int(self.pos[i]) for i in live]
        t0 = now_ns()
        with TraceAnnotation("bench.decode"):
            nxt = super()._decode_exec(live)
        self.decode_spans.append(Span(t0, now_ns(), positions))
        return nxt

    def clear_spans(self):
        self.prefill_spans = []
        self.decode_spans = []


@dataclasses.dataclass
class Sent:
    arrival: object           # traffic.Arrival
    prompt: np.ndarray
    due_ns: int = 0
    submit_ns: int = 0
    token_ns: list = dataclasses.field(default_factory=list)
    req: object = None        # the engine's Request


class Client:
    """Open loop: one thread sends the schedule on time."""

    def __init__(self, engine, schedule, prompts):
        self.engine = engine
        self.sent = [Sent(a, p) for a, p in zip(schedule, prompts)]
        self.start_ns = 0
        self._thread = threading.Thread(target=self._run, name="bench.client",
                                         daemon=True)
        self.error: BaseException | None = None

    def start(self, start_ns: int) -> "Client":
        self.start_ns = start_ns
        for s in self.sent:
            s.due_ns = start_ns + int(s.arrival.due_s * 1e9)
        self._thread.start()
        return self

    def _run(self):
        try:
            for s in self.sent:
                wait = (s.due_ns - now_ns()) / 1e9
                if wait > 0:
                    time.sleep(wait)
                stamps = s.token_ns

                def on_token(_tok, stamps=stamps):
                    stamps.append(now_ns())
                with TraceAnnotation("bench.submit"):
                    s.submit_ns = now_ns()
                    s.req = self.engine.submit(
                        s.prompt, max_new_tokens=s.arrival.output_len - 1,
                        on_token=on_token)
        except BaseException as e:  # reported by join(): the run fails
            self.error = e

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("the client thread did not finish")
        if self.error is not None:
            raise self.error

    def wait_done(self, deadline_ns: int) -> None:
        """Wait for every sent request until the deadline."""
        for s in self.sent:
            left = (deadline_ns - now_ns()) / 1e9
            if s.req is None or left <= 0:
                return
            s.req.done_event.wait(left)

    def lateness_ms(self) -> np.ndarray:
        return np.array([(s.submit_ns - s.due_ns) / 1e6 for s in self.sent
                         if s.submit_ns])
