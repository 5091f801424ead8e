"""Open-loop serving: requests arrive on a seeded Poisson schedule whatever
the engine does, so a slow engine builds a queue and its tail shows it.

Traffic parameters: ``rate_hz`` (offered rate), ``p_event`` (share of event
streams), ``event_t_choices`` (stream lengths, equally often), ``gain`` (rate
coding), ``flip_noise`` (digit noise).  Every request due in the window is
served to completion after the window closes, and every one counts in the
tail.  Latency runs from each request's nominal arrival to the moment its
own results are on the host.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import gen
import serving
import system
import work

CHECK_SAMPLE = 8192


def drive(engine, objs, arrivals, *, clock=time.perf_counter,
          sleep=time.sleep, trace=False):
    """Admit ``objs[i]`` at ``t0 + arrivals[i]`` and serve until every one is
    answered.  Only the requests not yet admitted are looked at; a serve
    drains and answers everything admitted.  Returns (t0, wake lags, serve
    durations), in s; a wake lag is how late the driver woke for a due
    arrival when it had slept."""
    n = len(objs)
    lags, calls = [], []
    i = 0
    woke_for = -np.inf   # the arrival a sleep waited for is due on waking
    t0 = clock()
    while True:
        now = max(clock() - t0, woke_for)
        j = int(np.searchsorted(arrivals, now, side="right"))
        if j > i:
            with serving.annotate(trace, "bench.submit"):
                engine.submit(objs[i:j])
            i = j
        if engine.queue_depth() == 0:
            if i >= n:
                break
            wait = arrivals[i] - (clock() - t0)
            if wait > 0:
                with serving.annotate(trace, "bench.idle_wait"):
                    sleep(wait)
                lags.append((clock() - t0) - arrivals[i])
            woke_for = arrivals[i]
            continue
        t_call = clock()
        with serving.annotate(trace, "bench.serve"):
            engine.serve()
        calls.append(clock() - t_call)
    return t0, np.asarray(lags, np.float64), calls


class Driver:
    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 clock=time.perf_counter, sleep=time.sleep):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.clock, self.sleep = clock, sleep

    def setup(self) -> None:
        cfg, tr = self.cell.config, self.cell.traffic
        t = [time.perf_counter()]
        self.network = system.Network(cfg, self.seed)
        self.engine = system.make_engine(cfg, self.network, self.cell.chips)
        t.append(time.perf_counter())
        self.arrivals = gen.poisson_arrivals(
            float(tr["rate_hz"]), self.seconds, self.seed)
        self.reqs = serving.Requests(
            len(self.arrivals), tr, self.seed,
            bool(cfg["engine"]["telemetry"]), self.clock)
        t.append(time.perf_counter())
        self.engine.warmup(event_ts=self.reqs.event_ts)
        t.append(time.perf_counter())
        print("set-up s: network and engine %.3f, requests %.3f, warm-up "
              "%.3f" % tuple(b - a for a, b in zip(t, t[1:])),
              file=sys.stderr, flush=True)

    def window(self) -> dict:
        before = serving.engine_counters(self.engine)
        t0, lags, calls = drive(self.engine, self.reqs.objs, self.arrivals,
                         clock=self.clock, sleep=self.sleep, trace=self.trace)
        done_at = np.array([np.nan if r.done_at is None else r.done_at
                            for r in self.reqs.objs])
        self.done = ~np.isnan(done_at)
        n_done = int(self.done.sum())
        t_end = float(np.nanmax(done_at)) if n_done else self.clock()
        window_s = t_end - t0
        lat_ms = (done_at[self.done] - (t0 + self.arrivals[self.done])) * 1e3
        if n_done:
            print("window latency ms: p50 %.3f p90 %.3f p99 %.3f max %.3f"
                  % tuple(np.percentile(lat_ms, [50, 90, 99, 100])),
                  flush=True)
        counters = serving.engine_counters(self.engine)
        topo = self.network.topology
        return {
            "window_s": window_s,
            "attempted": len(self.reqs),
            "failed": len(self.reqs) - n_done,
            "end_to_end": {
                "req_per_s": n_done / window_s,
                "latency_p90_ms": float(np.percentile(lat_ms, 90.0))
                if n_done else float("nan"),
            },
            "record": {
                "completed": n_done,
                "macs": self.reqs.macs(np.flatnonzero(self.done),
                                       work.macs_per_inference(topo)),
                "engine": serving.counter_delta(before, counters),
                "wake_lags_s": lags,
                "call_s": calls,
            },
        }

    def release(self) -> None:
        self.engine.close()
        self.engine = None

    def checks(self) -> list:
        idx = serving.sample_indices(self.done, self.reqs.t_of, CHECK_SAMPLE,
                                     self.seed)
        from harness import Check

        return serving.compare(self.reqs, idx, self.network,
                               self.cell.config) + [
            Check("unanswered", float((~self.done).sum()), 0.0)]

    def control(self) -> list:
        """The control's readings on the same requests."""
        idx = serving.sample_indices(self.done, self.reqs.t_of, CHECK_SAMPLE,
                                     self.seed)
        ans = serving.control_answers(self.reqs, idx, self.network,
                                      self.cell.config)
        return serving.compare(self.reqs, idx, self.network, self.cell.config,
                               answers=ans)
