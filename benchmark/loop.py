"""The measured window: when each request is due, when it was sent, and
what came back, on the benchmark's own clock.

A driver asks `due()` for the requests to send now, reports `sent`,
`token`, `done` and `failed` as they happen, and loops until
`finished()`.  Time 0 is the opening of the window; requests due before
it are the pre-roll, served and not counted.
"""

from __future__ import annotations

import time


class Window:
    def __init__(self, plan: dict, seconds: float, drain_s: float = 5.0,
                 trace_s: float = 0.0, on_trace=None):
        self.requests = plan["requests"]
        self.max_outstanding = plan["max_outstanding"]
        self.preroll_s = plan["preroll_s"]
        self.seconds, self.drain_s = float(seconds), float(drain_s)
        self.records = {r["id"]: {"due": r["due"], "sent": None,
                                  "first": None, "last": None, "done": None,
                                  "tokens": 0, "failed": False}
                        for r in self.requests}
        self._next = 0
        self.exhausted = False      # a backlog that ran out of supply
        self.outstanding = 0
        self.tokens_in_window = 0
        self.origin = None
        # a traced run traces the window's last `trace_s` seconds, so that
        # writing the trace out falls after the window and not inside it
        self._trace_at = self.seconds - trace_s if trace_s else None
        self._on_trace = on_trace
        self.trace_span = None

    def start(self) -> None:
        self.origin = time.perf_counter() + self.preroll_s

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def counted(self, record: dict) -> bool:
        return 0.0 <= record["due"] < self.seconds

    def due(self) -> list:
        """Requests to hand over now, oldest first."""
        now, ready = self.now(), []
        self._trace(now)
        while self._next < len(self.requests):
            request = self.requests[self._next]
            # an open loop hands over whatever was due in the window,
            # however late; a backlog stops feeding when the window closes
            if request["due"] > now or (
                    self.max_outstanding is not None and (
                        now >= self.seconds or self.outstanding + len(ready)
                        >= self.max_outstanding)):
                break
            ready.append(request)
            self._next += 1
        if self.max_outstanding is not None and now < self.seconds and \
                self._next >= len(self.requests):
            self.exhausted = True
        return ready

    def next_due(self) -> float | None:
        """Seconds until the next request is due (None: none left)."""
        if self._next >= len(self.requests) or \
                self.max_outstanding is not None:
            return None
        return self.requests[self._next]["due"] - self.now()

    def _trace(self, now: float) -> None:
        if self._trace_at is None:
            return
        if self.trace_span is None and now >= self._trace_at:
            self._on_trace(True)
            self.trace_span = [self.now(), None]
        elif self.trace_span and self.trace_span[1] is None and \
                now >= self.seconds:
            self.trace_span[1] = now
            self._on_trace(False)

    def sent(self, request_id: str) -> None:
        self.records[request_id]["sent"] = self.now()
        self.outstanding += 1

    def refused(self, request_id: str) -> None:
        record = self.records[request_id]
        record["sent"], record["failed"] = self.now(), True

    def token(self, request_id: str, count: int = 1) -> None:
        record, now = self.records[request_id], self.now()
        if record["first"] is None:
            record["first"] = now
        record["last"] = now
        record["tokens"] += count
        if 0.0 <= now < self.seconds:
            self.tokens_in_window += count

    def done(self, request_id: str) -> None:
        record = self.records[request_id]
        if record["done"] is None and not record["failed"]:
            record["done"] = self.now()
            self.outstanding -= 1

    def failed(self, request_id: str) -> None:
        record = self.records[request_id]
        if record["done"] is None and not record["failed"]:
            record["failed"] = True
            self.outstanding -= 1

    def finished(self) -> bool:
        now = self.now()
        self._trace(now)
        if now < self.seconds:
            return False
        if self.max_outstanding is not None or now >= self.seconds + \
                self.drain_s:
            return True
        return all(r["done"] is not None or r["failed"]
                   for r in self.records.values()
                   if r["due"] < self.seconds)

    def close(self) -> list:
        """The counted records; in an open loop whatever was due and has
        no result by now has failed."""
        if self.max_outstanding is None:
            counted = [r for r in self.records.values() if self.counted(r)]
            for record in counted:
                if record["done"] is None:
                    record["failed"] = True
            return counted
        # a backlog is never empty by design: what finished inside the
        # window was due in it, what was still queued or running was not
        return [r for r in self.records.values()
                if (r["done"] is not None and 0.0 <= r["done"] < self.seconds)
                or (r["failed"] and r["sent"] is not None
                    and 0.0 <= r["sent"] < self.seconds)]
