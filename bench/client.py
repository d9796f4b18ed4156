"""The benchmark's client: sends a cell's requests to ``AsyncServer``,
pumps it, and stamps every token as it reaches the client.

All times are ``time.perf_counter()`` seconds from the window's start.
Open loop: each request is submitted when it falls due (timed from its
due time, not from when the client got to it). Closed loop: each client
sends its next request when its last one finishes.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


class Client:
    def __init__(self, srv, engine, *, instrument: bool = False):
        self.srv = srv
        self.engine = engine
        self.instrument = instrument
        self.t0 = time.perf_counter()
        self.times: dict[int, list[float]] = {}
        self.due: dict[int, float] = {}
        self.submitted: dict[int, float] = {}
        self.live: set[int] = set()
        self.finished: set[int] = set()
        self.lateness: list[float] = []
        # per-pump (pump wall, engine wall) and per-engine-step records,
        # kept while ``recording`` (the traced slice)
        self.recording = False
        self.pumps: list[tuple[float, float]] = []
        self.steps: list[dict] = []
        self.admitted: list[int] = []
        self._eng_s = 0.0
        if instrument:
            self._wrap()

    # ---------------------------------------------------------------- spans
    def _span(self, name):
        if not self.instrument:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self):
        """Time and annotate the engine's step and admission and the
        server's fan-out, on these objects only."""
        eng, srv = self.engine, self.srv
        step, admit, fanout = eng.step, eng._admit, srv._fanout

        def timed_step():
            with self._span("engine_step"):
                t = time.perf_counter()
                stats = step()
                dt = time.perf_counter() - t
            self._eng_s += dt
            if self.recording:
                reads = np.asarray(stats.get("tier_reads", np.zeros(3)))
                self.steps.append({"dt": dt, "active": int(stats["active"]),
                                   "reads": int(reads.sum()),
                                   "paged_reads": int(reads[1:].sum()),
                                   "prefill_tokens":
                                       int(stats["prefill_tokens"])})
            return stats

        def timed_admit():
            with self._span("admission"):
                return admit()

        def timed_fanout():
            with self._span("fanout"):
                return fanout()

        eng.step, eng._admit, srv._fanout = timed_step, timed_admit, \
            timed_fanout

    # ---------------------------------------------------------------- core
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, req, due: float | None = None) -> None:
        with self._span("submit"):
            self.srv.submit(req.prompt, req.max_new, rid=req.id)
        t = self.now()
        self.submitted[req.id] = t
        self.due[req.id] = t if due is None else due
        if due is not None:
            self.lateness.append(t - due)
        self.times[req.id] = []
        self.live.add(req.id)

    def pump(self) -> list[int]:
        """One ``AsyncServer.step()``; returns the requests that finished."""
        self._eng_s = 0.0
        with self._span("pump"):
            t = time.perf_counter()
            self.srv.step()
            wall = time.perf_counter() - t
        if self.recording:
            self.pumps.append((wall, self._eng_s))
        now = self.now()
        done = []
        for rid in list(self.live):
            rec = self.srv.records[rid]
            ts = self.times[rid]
            new = len(rec.tokens) - len(ts)
            if new > 0:
                if not ts and self.recording:
                    self.admitted.append(rec.prompt_len)
                ts.extend([now] * new)
            if rec.done:
                self.live.discard(rid)
                self.finished.add(rid)
                done.append(rid)
        return done

    def serve_one_by_one(self, reqs, pumps: int = 3) -> None:
        """Submit ``reqs`` one at a time, each admitted before the next
        is sent (the first wave of a closed loop, made during set-up). A
        request the pool cannot take yet stays queued after ``pumps``
        tries, as would the rest of the wave."""
        for r in reqs:
            self.submit(r)
            for _ in range(pumps):
                if self.times[r.id]:
                    break
                self.pump()

    def run_until_done(self, rids) -> None:
        rids = set(rids)
        while rids & self.live:
            self.pump()

    # --------------------------------------------------------------- loops
    def open_loop(self, reqs, window: float, grace: float,
                  on_time=None) -> float:
        """Submit ``reqs`` at their due times, pump through the window and
        the grace period. Returns the end of grace."""
        i = 0
        while True:
            now = self.now()
            if on_time is not None:
                on_time(now)
            while i < len(reqs) and reqs[i].due <= now:
                self.submit(reqs[i], due=reqs[i].due)
                i += 1
            if self.live:
                self.pump()
            elif i < len(reqs):
                time.sleep(min(max(reqs[i].due - now, 0.0), 0.0005))
            elif now >= window:
                return now
            if now >= window + grace:
                return now

    def closed_loop(self, reqs, window: float, grace: float,
                    on_time=None) -> float:
        """Each client owns one request in flight and sends the next of
        ``reqs`` when it finishes, until the window closes; then pump
        until everything in flight finishes or grace ends. Returns the
        end of grace."""
        queue = list(reqs)
        while True:
            now = self.now()
            if on_time is not None:
                on_time(now)
            if not self.live or now >= window + grace:
                return now
            for _ in self.pump():
                if self.now() < window and queue:
                    self.submit(queue.pop(0), due=self.now())
