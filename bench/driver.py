"""The measured run: drives ``ServeLoop.submit`` / ``ServeLoop.tick`` with
a mix's traffic and keeps the client's clock.

Each request carries two stamps taken here, never the program's own:
``start`` (when it was due, open loop; when the client sent it, closed
loop) and ``seen`` (the end of the first ``tick()`` after which it was in
``loop.done``).  The window is ``[win_start, win_end)`` on the host clock;
the requests of the window are those whose ``start`` falls in it.  After
the window the run follows every request it submitted, the warm-up's
too, to completion, for at most ``GRACE_S`` seconds.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

GRACE_S = 60.0               # a request unfinished this long after the
#                              window closes never came
SAMPLE_P = 1.0 / 128         # share of the ticks the checks replay
MAX_SAMPLES = 48
IDLE_WARM_TICKS = 3          # all-padding ticks that compile serve_step
STALL_S = 0.05               # a tick this long is reported as a stall
TRACE_S = 3.0                # the traced run profiles the window's last
#                              seconds (at most half the window)


class Recorder:
    """Wraps the loop's ``serve_step`` and keeps, for the first tick of
    traffic and a seeded sample of the later ones, the step's inputs and
    outputs (device arrays held
    by reference, the application cache left out).  With ``annotate`` it
    marks each call as a host span for the traced run."""

    def __init__(self, step, seed: int, annotate=None):
        self.step = step
        self.rng = np.random.default_rng([seed, 7])
        self.annotate = annotate
        self.active = False
        self.calls = 0
        self.samples: list = []
        self.fault = None     # a test may break the step underneath

    def __call__(self, params, state, reqs):
        self.calls += 1
        step = self.step if self.fault is None else self.fault(self.step)
        if self.annotate is not None:
            with self.annotate("serve_step"):
                new, out = step(params, state, reqs)
        else:
            new, out = step(params, state, reqs)
        if self.active and len(self.samples) < MAX_SAMPLES and (
                not self.samples or self.rng.random() < SAMPLE_P):
            self.samples.append((state._replace(cache=None), reqs,
                                 new._replace(cache=None), out))
        return new, out


@dataclasses.dataclass
class RunLog:
    start: np.ndarray         # per request id: start stamp (s)
    seen: np.ndarray          # per request id: seen-done stamp, nan if not
    dropped: np.ndarray       # per request id: bool
    win_start: float
    win_end: float
    ticks_in_window: int
    completed_in_window: int  # requests seen done inside the window
    lateness: np.ndarray      # open loop: submit − due per submitted request
    ticks: np.ndarray = None  # (start, seconds) of every tick of the run
    collections: np.ndarray = None   # (start, seconds, generation) of
    #                                  every garbage collection of the run

    def in_window(self) -> np.ndarray:
        return (self.start >= self.win_start) & (self.start < self.win_end)

    def stalls(self, over_s: float = STALL_S) -> str:
        """The window's slow ticks and garbage collections, in one line."""
        t, d = self.ticks[:, 0], self.ticks[:, 1]
        w = (t >= self.win_start) & (t < self.win_end)
        t, d = t[w], d[w]
        c = self.collections
        c = c[(c[:, 0] >= self.win_start) & (c[:, 0] < self.win_end)]
        slow = d > over_s
        with_gc = sum(bool(np.any((c[:, 0] >= a) & (c[:, 0] < a + b)
                                  & (c[:, 2] == 2)))
                      for a, b in zip(t[slow], d[slow]))
        gen = {g: c[c[:, 2] == g, 1] for g in (0, 1, 2)}
        top = np.sort(d)[::-1][:5] * 1e3
        fifth = max(d.size // 5, 1)
        return (f"ticks {d.size}: median {np.median(d) * 1e3:.3f} ms "
                f"(first fifth {np.median(d[:fifth]) * 1e3:.3f}, last "
                f"{np.median(d[-fifth:]) * 1e3:.3f}), "
                f"p99 {np.percentile(d, 99) * 1e3:.3f} ms, longest "
                f"{', '.join(f'{x:.1f}' for x in top)} ms; "
                f"{int(slow.sum())} over {over_s * 1e3:.0f} ms took "
                f"{d[slow].sum():.3f} s, {with_gc} of them with a full "
                f"collection; collections (count, s, longest ms) "
                + ", ".join(f"gen{g} {v.size} {v.sum():.3f} "
                            f"{v.max(initial=0) * 1e3:.1f}"
                            for g, v in gen.items()))


class Driver:
    """One measured run of a deployment under a mix."""

    def __init__(self, dep, mix, seconds: float, *, trace_dir=None):
        import jax
        from repro.runtime.serve_loop import Request
        self.Request = Request
        self.dep, self.mix, self.seconds = dep, mix, float(seconds)
        self.loop = dep.loop
        self.trace_dir = trace_dir
        self.annotate = (jax.profiler.TraceAnnotation if trace_dir
                         else None)
        self.rec = Recorder(self.loop.serve_step, mix.seed, self.annotate)
        self.loop.serve_step = self.rec
        self.start: list[float] = []
        self.client: list[int] = []
        self.seen_done = 0
        self.seen_dropped = 0
        self._seen: dict[int, float] = {}
        self._dropped: set = set()
        self._tracing = False
        self._ticks: list = []
        self._collections: list = []
        self._gc_at = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_at = now
        else:
            self._collections.append((self._gc_at, now - self._gc_at,
                                      info["generation"]))

    def span(self, name: str):
        return (self.annotate(name) if self.annotate is not None
                else contextlib.nullcontext())

    # ------------------------------------------------------------------ #
    def compile(self) -> None:
        """Compile and load every program the window runs: all-padding
        ticks trace ``serve_step`` (both admission branches, the kernels'
        tile plans) before any request is due."""
        for _ in range(IDLE_WARM_TICKS):
            self.loop.tick()

    def submit(self, svc_cls: int, user: int, token: int, nbytes: int,
               start: float, client: int = -1) -> None:
        rid = len(self.start)
        cls = self.mix.classes[svc_cls]
        self.start.append(start)
        self.client.append(client)
        self.loop.submit(self.Request(
            req_id=rid, service=self.dep.service_ids[cls["service"]],
            headers=self.mix.headers(svc_cls, user),
            prompt_token=int(token), msg_bytes=int(nbytes)))

    def tick(self) -> list:
        """One ``tick()``; returns the ids it finished, stamped."""
        t0 = time.perf_counter()
        with self.span("tick"):
            self.loop.tick()
        now = time.perf_counter()
        self._ticks.append((t0, now - t0))
        new = self.loop.done[self.seen_done:]
        self.seen_done = len(self.loop.done)
        for r in new:
            self._seen[r.req_id] = now
        for r in self.loop.dropped[self.seen_dropped:]:
            self._dropped.add(r.req_id)
        self.seen_dropped = len(self.loop.dropped)
        self._trace_step(now)
        return new

    def _trace_step(self, now: float) -> None:
        """Start the profiler for the window's last seconds (Python calls
        untraced, host annotations kept); ``_trace_stop`` ends it once the
        window has closed, so its write-out stalls no measured tick."""
        if self.trace_dir is None or self._tracing or now < self._trace_at:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._tracing = True
        self.trace_dir = None

    def _trace_stop(self) -> None:
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    # ------------------------------------------------------------------ #
    def run(self) -> RunLog:
        gc.callbacks.append(self._on_gc)
        try:
            t0 = time.perf_counter()
            win_start = t0 + self.mix.warmup_s
            win_end = win_start + self.seconds
            self._trace_at = win_end - min(TRACE_S, self.seconds / 2)
            self.rec.active = True           # replay samples from the first
            if self.mix.loop == "open":      # tick of traffic on
                lateness, ticks, done_in = self._open(t0, win_start, win_end)
            else:
                lateness, ticks, done_in = self._closed(t0, win_start, win_end)
            t_exit = time.perf_counter()
            self._trace_stop()
            self.rec.active = False
            self._drain(t_exit)
        finally:
            gc.callbacks.remove(self._on_gc)
        n = len(self.start)
        seen = np.full(n, np.nan)
        for rid, t in self._seen.items():
            seen[rid] = t
        dropped = np.zeros(n, bool)
        dropped[list(self._dropped)] = True
        return RunLog(np.asarray(self.start), seen, dropped, win_start,
                      t_exit, ticks, done_in, np.asarray(lateness),
                      np.asarray(self._ticks).reshape(-1, 2),
                      np.asarray(self._collections).reshape(-1, 3))

    def _open(self, t0, win_start, win_end):
        due, reqs = self.mix.schedule(self.seconds)
        due = due + t0
        n_due = int(np.searchsorted(due, win_end))
        lateness = np.zeros(n_due)
        i = ticks = done_in = 0
        now = t0
        while now < win_end:
            with self.span("submit"):
                while i < n_due and due[i] <= now:
                    self.submit(reqs.cls[i], reqs.user[i], reqs.token[i],
                                reqs.msg_bytes[i], due[i])
                    lateness[i] = now - due[i]
                    i += 1
            in_win = win_start <= now
            finished = self.tick()
            now = time.perf_counter()
            if in_win:
                ticks += 1
                done_in += len(finished)
        while i < n_due:                       # due in the window, late
            self.submit(reqs.cls[i], reqs.user[i], reqs.token[i],
                        reqs.msg_bytes[i], due[i])
            lateness[i] = now - due[i]
            i += 1
        return lateness, ticks, done_in

    def _closed(self, t0, win_start, win_end):
        tpl = self.mix.templates()
        k = [0] * self.mix.clients(self.dep.n_slots)

        def send(c, now):
            j = self.mix.template_index(c, k[c])
            k[c] += 1
            self.submit(tpl.cls[j], tpl.user[j], tpl.token[j],
                        tpl.msg_bytes[j], now, client=c)

        with self.span("submit"):
            for c in range(len(k)):
                send(c, t0)
        ticks = done_in = 0
        now = t0
        while now < win_end:
            in_win = win_start <= now
            finished = self.tick()
            now = time.perf_counter()
            if in_win:
                ticks += 1
                done_in += len(finished)
            if now < win_end:
                with self.span("submit"):
                    for r in finished:
                        send(self.client[r.req_id], now)
        return np.zeros(0), ticks, done_in

    def _drain(self, t_exit: float) -> None:
        """Tick until every request submitted (the warm-up's too) is done
        or dropped, or the grace period has passed."""
        pending = set(range(len(self.start)))
        pending -= set(self._seen) | self._dropped
        deadline = t_exit + GRACE_S
        while pending and time.perf_counter() < deadline:
            for r in self.tick():
                pending.discard(r.req_id)
            pending -= self._dropped
