"""The wall-clock driver: feeds `ServingEngine.step()` in real time.

The engine admits requests against its own modeled clock, so a request
is handed over when it falls due on the wall clock with
`arrival_s = engine.clock`, and the next `step()` admits it. After each
`step()` returns, every live request's new `out_tokens` are stamped with
the wall time: a step that samples ends in a host sync (`np.asarray` of
the sampled ids), so a token is stamped once it exists on the host.

Open loop: requests fall due on the traffic's schedule whether or not
earlier ones are done. Closed loop: `concurrency` requests are always
outstanding; a finished one is replaced at once.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional

from chipbench.lib.stats import Track
from chipbench.lib.traffic import Draw


@dataclasses.dataclass
class StepRecord:
    t_start: float
    t_end: float
    model_dt: float          # the engine's modeled duration of the step (perfmodel)
    kv_used: int             # pool blocks held after the step
    tokens: int              # tokens emitted by the step
    outstanding: int         # requests handed over and not finished, after the step


class Driver:
    def __init__(self, engine, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.tracks: list[Track] = []
        self.live: list = []            # [request, track]
        self.steps: list[StepRecord] = []
        self.finished: list = []        # (request, track) of finished requests
        self.late: list[float] = []     # how late each open-loop hand-over was (s)

    # ------------------------------------------------------------ requests
    def submit(self, draw: Draw, due: float, stream: int) -> Track:
        now = self.clock()
        r = self.engine.submit(draw.prompt, max_new_tokens=draw.max_new,
                               arrival_s=self.engine.clock)
        tr = Track(due=due, stream=stream, submitted=now, max_new=draw.max_new,
                   prompt_len=len(draw.prompt))
        self.tracks.append(tr)
        self.live.append([r, tr])
        return tr

    def outstanding(self) -> int:
        return len(self.live)

    # ---------------------------------------------------------------- step
    def step(self) -> bool:
        """One engine step; stamps the tokens it emitted. False when the
        engine had nothing to do."""
        eng = self.engine
        m0 = eng.clock
        t0 = self.clock()
        busy = eng.step()
        t1 = self.clock()
        if not busy:
            return False
        emitted = 0
        keep = []
        for item in self.live:
            r, tr = item
            n = len(r.out_tokens)
            new = n - len(tr.token_times)
            if new > 0:
                tr.token_times.extend([t1] * new)
                emitted += new
            if r.done or r.status != "ok":
                self.finished.append((r, tr))
            else:
                keep.append(item)
        self.live = keep
        pool = eng.pool
        self.steps.append(StepRecord(t0, t1, eng.clock - m0,
                                     pool.num_blocks - pool.free_blocks, emitted,
                                     len(self.live)))
        return True

    # ---------------------------------------------------------------- loops
    def run_open(self, source: Iterator[Draw], base: float, until: float,
                 stream: int, pending: Optional[Draw] = None) -> Optional[Draw]:
        """Hand over every draw of `source` as it falls due (its `due_s`
        after `base`) and step the engine, until the wall clock reaches
        `until`. Returns the first draw not yet due, to carry on with."""
        nxt = pending if pending is not None else next(source)
        while True:
            now = self.clock()
            if now >= until:
                return nxt
            while base + nxt.due_s <= now:
                due = base + nxt.due_s
                self.late.append(now - due)
                self.submit(nxt, due, stream)
                nxt = next(source)
            if not self.step():
                # idle: wait for the next due request, but not past `until`
                wait = min(base + nxt.due_s, until) - self.clock()
                if wait > 0:
                    time.sleep(wait)

    def run_closed(self, source: Iterator[Draw], concurrency: int, until: float,
                   stream: int, done: Optional[Callable[[], bool]] = None) -> None:
        """Keep `concurrency` requests outstanding until `until`, or until
        `done()` holds."""
        while self.clock() < until and not (done and done()):
            while self.outstanding() < concurrency:
                now = self.clock()
                self.submit(next(source), now, stream)
            self.step()
