"""Serve a backlog larger than the window can drain: every lane stays busy,
so the window measures steady decode at the backlog's context lengths.

The backlog is submitted and the first batch admitted during set-up; the
window then steps the engine until ``seconds`` have passed.
"""

import time

from serving import ServeDriver


class Driver(ServeDriver):
    def setup(self) -> None:
        super().setup()
        for r in self.requests:
            self.submit(r)
        self.step()                     # admit the first full batch

    def window(self, seconds: float) -> None:
        self.counting = True
        self.t0 = time.perf_counter()
        while time.perf_counter() - self.t0 < seconds:
            self.step()
        self.t_end = time.perf_counter()
        self.counting = False
        if not self.engine.waiting:
            raise RuntimeError("the backlog ran dry inside the window; the "
                               "mix needs more requests")

    def after_window(self) -> None:
        pass

    def candidates(self):
        return [rid for rid, t in self.token_times.items() if t]

    def attempted_failed(self):
        """Requests admitted in the window or before it, and how many of
        them expired or were shed."""
        reqs = [self.reqs[rid] for rid in self.admitted]
        return len(reqs), sum(1 for r in reqs if r.expired)
