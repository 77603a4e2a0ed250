"""Serve an open loop: requests are submitted when due, whatever the engine
is doing, at the rate fixed in the mix.  Time to first token runs from
the due time, so a stall counts against every request waiting behind it.

After the window closes no request is submitted; the engine steps on until
every request due in the window has its first token, for at most a
minute, and a request still without one then counts as failed.
"""

import time

import jax

from serving import ServeDriver, percentile

DRAIN_S = 60.0


class Driver(ServeDriver):
    def setup(self) -> None:
        super().setup()
        self.next = 0
        self.late = []                  # submit time - due time, seconds

    def _submit_due(self, elapsed: float) -> None:
        reqs = self.requests
        while self.next < len(reqs) and reqs[self.next]["due"] <= elapsed:
            with jax.profiler.TraceAnnotation("bench.submit"):
                self.submit(reqs[self.next])
            self.late.append(elapsed - reqs[self.next]["due"])
            self.next += 1

    def window(self, seconds: float) -> None:
        self.counting = True
        self.seconds = seconds
        eng = self.engine
        self.t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - self.t0
            if elapsed >= seconds:
                break
            self._submit_due(elapsed)
            if eng.waiting or eng.active:
                self.step()
            else:
                nxt = (self.requests[self.next]["due"]
                       if self.next < len(self.requests) else seconds)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, seconds) - elapsed))
        self.t_end = time.perf_counter()
        self.counting = False
        self.waiting_at_close = len(eng.waiting)

    def after_window(self) -> None:
        self._submit_due(self.seconds)       # due before the close, not sent
        due = self.requests[:self.next]
        start = time.perf_counter()
        while time.perf_counter() - start < DRAIN_S and any(
                not self.token_times.get(r["rid"]) and not self._expired(r)
                for r in due):
            self.step()
        self.t_drained = time.perf_counter()

    def _expired(self, r) -> bool:
        req = self.reqs.get(r["rid"])
        return req is not None and req.expired

    def ttfts(self):
        """(time to first token, failed) of every request due in the
        window; a failed request is charged all the time it waited."""
        out = []
        for r in self.requests[:self.next]:
            times = self.token_times.get(r["rid"])
            failed = not times or self._expired(r)
            end = self.t_drained if failed else times[0]
            out.append((end - (self.t0 + r["due"]), failed))
        return out

    def ttft_thirds(self):
        """Median time to first token of the requests due in each third of
        the window: level while the engine keeps up, rising when its queue
        grows."""
        thirds = [[], [], []]
        for r, (t, _) in zip(self.requests, self.ttfts()):
            thirds[min(2, int(3 * r["due"] / self.seconds))].append(t)
        return [percentile(x, 50) if x else None for x in thirds]

    def layer_data(self) -> dict:
        out = super().layer_data()
        ttft_p95 = 1e3 * percentile([t for t, _ in self.ttfts()], 95)
        out["load"] = {"ttft_p95_ms": ttft_p95,
                        "late_max_s": max(self.late, default=0.0),
                        "late_mean_s": sum(self.late) / max(1, len(self.late)),
                        "waiting_at_close": self.waiting_at_close,
                        "due": self.next,
                        "ttft_median_s_thirds": self.ttft_thirds()}
        return out

    def candidates(self):
        return [rid for rid, req in self.reqs.items()
                if len(req.generated) == req.max_new_tokens and not req.expired]

    def attempted_failed(self):
        t = self.ttfts()
        return len(t), sum(1 for _, failed in t if failed)
