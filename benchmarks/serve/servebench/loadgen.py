"""The load generator: one thread that sends every request and reads
every token, through the engine's public session API.

It is the single producer of every session it opens (``connect`` ->
``submit_i``) and the single consumer of their streams (``pump``).  It
runs beside the engine's thread in the same process, so it competes
with the scheduler for the interpreter lock just as an in-process
client of the engine does; that hand-off is part of what is measured.

Open loop: each session is due at its arrival time and each later turn
``turn_gap_s`` after the previous answer's last token, whether or not
the engine keeps up.  Every request is timed from when it was due, and
how late the generator sent it is recorded.  Closed loop: each client
sends its next request as soon as its previous one is done; the
window opens once ``max_batch`` requests have produced a token, so
every slot is full when it opens.  Either way, nothing is sent after
the window closes, and what was sent is waited for, up to
``drain_s``.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from servebench.traffic import Schedule


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    session: int
    turn: int
    client: int
    due: float
    prompt: np.ndarray
    max_tokens: int
    sent: float = float("nan")
    submit_s: float = 0.0
    handle: Optional[object] = None
    tokens: Dict[int, int] = dataclasses.field(default_factory=dict)
    stamps: Dict[int, float] = dataclasses.field(default_factory=dict)
    done: Optional[float] = None
    error: Optional[str] = None
    stream_late: int = 0        # tokens that came only with the terminal
    mismatched: int = 0         # streamed tokens the terminal contradicts

    @property
    def first(self) -> Optional[float]:
        return self.stamps.get(0)

    def out(self) -> List[int]:
        return [self.tokens[p] for p in sorted(self.tokens)]

    def out_stamps(self) -> List[float]:
        return [self.stamps[p] for p in sorted(self.stamps)]

    @property
    def ok(self) -> bool:
        return (self.done is not None and self.error is None
                and len(self.tokens) == self.max_tokens
                and self.mismatched == 0)


@dataclasses.dataclass
class Outcome:
    recs: List[Rec]
    t_open: float
    t_close: float
    stats_open: Dict[str, int]          # engine counters at the opening
    loop_gap_max_s: float = 0.0         # longest pass of the loop, in it


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class LoadGen:
    def __init__(self, eng, sched: Schedule, seconds: float,
                 max_batch: int, annotate: Optional[Callable] = None,
                 drain_s: float = 60.0, span_s: Optional[float] = None,
                 on_span_end: Optional[Callable] = None,
                 lead_cap_s: float = 60.0):
        self.eng, self.sched, self.seconds = eng, sched, float(seconds)
        self.max_batch = max_batch
        self.span = annotate or (lambda name: _Null())
        self.drain_s = drain_s
        # The ``bench.window`` span (the traced part of the window) runs
        # from the window's opening for ``span_s``; then ``on_span_end``.
        self.span_s = self.seconds if span_s is None else min(span_s,
                                                               self.seconds)
        self.on_span_end = on_span_end
        self.lead_cap_s = lead_cap_s
        self.clock = time.perf_counter
        self.sessions = [eng.connect(c) for c in range(sched.clients)]
        self.history: Dict[int, List[np.ndarray]] = {}
        self.by_client: List[List[int]] = [[] for _ in range(sched.clients)]
        for s in sched.sessions:
            self.by_client[s.client].append(s.index)
        self.next_of_client = [0] * sched.clients

    # -- what to send ---------------------------------------------------
    def _prompt(self, s, turn: int, answers: List[np.ndarray]) -> np.ndarray:
        parts = [self.sched.system]
        for t in range(turn + 1):
            parts.append(self.sched.user_tokens(s.index, t,
                                                s.turns[t].user_len))
            if t < turn:
                parts.append(answers[t])
        return np.concatenate(parts).astype(np.int32)

    def _make(self, s, turn: int, due: float) -> Rec:
        answers = self.history.get(s.index, []) if turn else []
        if not turn:
            self.history.pop(s.index, None)
        return Rec(s.index, turn, s.client, due,
                   self._prompt(s, turn, answers), s.turns[turn].answer_len)

    def _send(self, rec: Rec) -> None:
        sess = self.sessions[rec.client]
        with self.span("bench.submit"):
            t0 = self.clock()
            rec.handle = sess.submit_i(rec.prompt, max_tokens=rec.max_tokens,
                                       eos_id=-1)
            rec.sent = self.clock()
        rec.submit_s = rec.sent - t0
        if rec.handle.status is not None:        # refused at submit time
            rec.error = f"refused: {rec.handle.status}"
            rec.done = rec.sent

    # -- what came back -------------------------------------------------
    def _collect(self, rec: Rec, now: float) -> bool:
        """Take the handle's streamed tokens; True once terminal."""
        h = rec.handle
        if not h.submitted and not h.done:
            h.test()                    # retries a send the ring refused
        q = h._tokens                   # the queue ``tokens()`` reads
        while q:
            pos, tok = q.popleft()
            if pos not in rec.tokens:
                rec.tokens[pos] = tok
                rec.stamps[pos] = now
        if not h.done:
            if self.eng.dead is not None:
                h.test()                # finalizes with FailedStatus
            if not h.done:
                return False
        final = h.response
        if h.status is not None or final is None:
            rec.error = f"terminal status {h.status}"
        out = [] if final is None or final.tokens_out is None else \
            [int(t) for t in final.tokens_out]
        for pos, tok in enumerate(out):
            if pos not in rec.tokens:
                rec.tokens[pos] = tok
                rec.stamps[pos] = now
                rec.stream_late += 1
            elif rec.tokens[pos] != tok:
                rec.mismatched += 1
        rec.done = now
        return True

    # -- the loop -------------------------------------------------------
    def run(self) -> Outcome:
        sched, closed = self.sched, self.sched.loop == "closed"
        start = self.clock()
        heap: List = []
        seq = 0
        if closed:
            for c in range(sched.clients):
                s = sched.sessions[self.by_client[c][0]]
                self.next_of_client[c] = 1
                heapq.heappush(heap, (start, seq, s.index, 0))
                seq += 1
            t_open, t_close = None, None
        else:
            for s in sched.sessions:
                heapq.heappush(heap, (start + s.arrival_s, seq, s.index, 0))
                seq += 1
            t_open, t_close = start, start + self.seconds
        recs: List[Rec] = []
        live: Dict[int, List[Rec]] = {}
        stats_open = dict(self.eng.stats) if not closed else None
        firsts = 0
        window_span = None
        if not closed:
            window_span = self.span("bench.window")
            window_span.__enter__()
        closed_yet = False
        gap_max, last = 0.0, None
        while True:
            now = self.clock()
            if t_open is not None and not closed_yet:
                if last is not None:
                    gap_max = max(gap_max, now - last)
                last = now
            if closed and t_open is None and (
                    firsts >= self.max_batch or now - start > self.lead_cap_s):
                t_open, t_close = now, now + self.seconds
                stats_open = dict(self.eng.stats)
                window_span = self.span("bench.window")
                window_span.__enter__()
            if (window_span is not None and t_open is not None
                    and now >= t_open + self.span_s):
                window_span.__exit__(None, None, None)
                window_span = None
                if self.on_span_end is not None:
                    self.on_span_end()
                now = self.clock()
            if t_close is not None and now >= t_close:
                closed_yet = True
            while heap and heap[0][0] <= now and not closed_yet:
                due, _, si, turn = heapq.heappop(heap)
                rec = self._make(sched.sessions[si], turn, due)
                self._send(rec)
                recs.append(rec)
                if rec.done is None:
                    live.setdefault(rec.client, []).append(rec)
                else:
                    seq = self._follow(rec, heap, seq)
            moved = False
            for c in list(live):
                with self.span("bench.pump"):
                    moved = self.sessions[c].pump() or moved
                    now = self.clock()
                    still = []
                    for rec in live[c]:
                        had_first = rec.first is not None
                        if not self._collect(rec, now):
                            still.append(rec)
                        if not had_first and rec.first is not None:
                            firsts += 1
                        if rec.done is not None:
                            moved = True
                            seq = self._follow(rec, heap, seq)
                if still:
                    live[c] = still
                else:
                    del live[c]
            if closed_yet and not live:
                break
            if closed_yet and now > t_close + self.drain_s:
                for rs in live.values():
                    for rec in rs:
                        rec.error = rec.error or "never finished"
                break
            if not moved:
                nxt = heap[0][0] - now if heap and not closed_yet else 1e-3
                time.sleep(min(max(nxt, 0.0), 5e-4))
        return Outcome(recs, t_open, t_close, stats_open, gap_max)

    def _follow(self, rec: Rec, heap: List, seq: int) -> int:
        """Queue what the finished request leads to: the session's next
        turn ``turn_gap_s`` after its last token, or (closed loop) the
        client's next session at once.  A failed request ends its
        session."""
        s = self.sched.sessions[rec.session]
        if rec.ok and rec.turn + 1 < len(s.turns):
            self.history.setdefault(s.index, []).append(
                np.asarray(rec.out(), np.int32))
            last = max(rec.stamps.values())
            heapq.heappush(heap, (last + self.sched.turn_gap_s(), seq,
                                  s.index, rec.turn + 1))
            return seq + 1
        if self.sched.loop == "closed":
            c = rec.client
            order = self.by_client[c]
            nxt = order[self.next_of_client[c] % len(order)]
            self.next_of_client[c] += 1
            heapq.heappush(heap, (rec.done, seq, nxt, 0))
            return seq + 1
        return seq
