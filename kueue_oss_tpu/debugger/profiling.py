"""Profiling + tracing endpoints.

Reference parity: the reference exposes Go pprof via the manager's
pprofBindAddress (apis/config PprofBindAddress; pkg/config/config_test.go
:251) and structured per-phase log timing. The Python analogs here:

- `Profiler`: cProfile sessions with pstats summaries — the
  /debug/pprof/profile equivalent for the host scheduling path;
- `Tracer`: lightweight span recording with Chrome-trace JSON export
  (chrome://tracing / Perfetto-loadable, the same workflow used for
  JAX/XLA device traces); `attach_to_scheduler` registers it as a sink
  of the program's one span primitive (`obs/spans.py`).
"""

from __future__ import annotations

import cProfile
import io
import json
import pstats
import sys
import threading
import time
from contextlib import contextmanager
from typing import Optional


class Profiler:
    """cProfile session manager (pprof 'profile' endpoint analog)."""

    def __init__(self) -> None:
        self._profile: Optional[cProfile.Profile] = None
        self._lock = threading.Lock()

    @property
    def running(self) -> bool:
        return self._profile is not None

    def start(self) -> None:
        with self._lock:
            if self._profile is not None:
                raise RuntimeError("profiler already running")
            self._profile = cProfile.Profile()
            self._profile.enable()

    def stop(self, top: int = 30, sort: str = "cumulative") -> str:
        """Stop and return a pstats text summary of the top functions."""
        with self._lock:
            if self._profile is None:
                raise RuntimeError("profiler not running")
            self._profile.disable()
            buf = io.StringIO()
            stats = pstats.Stats(self._profile, stream=buf)
            stats.sort_stats(sort).print_stats(top)
            self._profile = None
            return buf.getvalue()

    @contextmanager
    def profile(self, top: int = 30):
        """Context manager yielding a result holder; holder['report']
        has the summary after the block exits."""
        holder: dict = {}
        self.start()
        try:
            yield holder
        finally:
            holder["report"] = self.stop(top=top)


class SamplingProfiler:
    """Statistical whole-process profiler (py-spy style).

    cProfile instruments only the calling thread, so it cannot see a
    scheduler serving in its own thread. This sampler walks
    ``sys._current_frames()`` — every thread's live stack — at a fixed
    interval and aggregates leaf/stack counts; it is what the
    /debug/pprof/profile endpoint uses.
    """

    def __init__(self, interval: float = 0.005,
                 max_depth: int = 40) -> None:
        self.interval = interval
        self.max_depth = max_depth
        self._lock = threading.Lock()
        self._stop: Optional[threading.Event] = None
        self._thread: Optional[threading.Thread] = None
        #: a fixed-window sample_for() is in flight (distinct from the
        #: background-session _thread; both exclude each other)
        self._busy = False
        self._leaf_counts: dict[str, int] = {}
        self._stack_counts: dict[tuple, int] = {}
        self._samples = 0
        self._started_at = 0.0

    @property
    def running(self) -> bool:
        return self._thread is not None

    def _sample_once(self, skip_tids: set) -> None:
        for tid, frame in sys._current_frames().items():
            if tid in skip_tids:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < self.max_depth:
                code = f.f_code
                stack.append(
                    f"{code.co_name} "
                    f"({code.co_filename.rsplit('/', 1)[-1]}"
                    f":{f.f_lineno})")
                f = f.f_back
            if not stack:
                continue
            self._samples += 1
            self._leaf_counts[stack[0]] = (
                self._leaf_counts.get(stack[0], 0) + 1)
            key = tuple(reversed(stack))
            self._stack_counts[key] = self._stack_counts.get(key, 0) + 1

    def _report(self, seconds: float, top: int) -> str:
        lines = [f"{self._samples} samples over {seconds:.2f}s "
                 f"({self.interval * 1000:.0f}ms interval)", "",
                 "top functions (leaf samples):"]
        for name, n in sorted(self._leaf_counts.items(),
                              key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {n:6d}  {name}")
        lines += ["", "top stacks:"]
        for stack, n in sorted(self._stack_counts.items(),
                               key=lambda kv: -kv[1])[:5]:
            lines.append(f"  {n:6d} samples:")
            for fr in stack[-10:]:
                lines.append(f"          {fr}")
        return "\n".join(lines)

    def _reset(self) -> None:
        self._leaf_counts = {}
        self._stack_counts = {}
        self._samples = 0

    def sample_for(self, seconds: float, top: int = 30) -> str:
        """Blocking window: sample every thread but this one for
        ``seconds``, return the aggregated report. The lock guards only
        the admission check — holding it across the window would make
        concurrent start/stop requests block for ``seconds`` and then
        run anyway, instead of failing fast with the 409 the endpoints
        promise."""
        with self._lock:
            if self._thread is not None or self._busy:
                raise RuntimeError(
                    "a sampling session is active; stop it "
                    "first (/debug/pprof/sample/stop)")
            self._busy = True
            self._reset()
        try:
            me = {threading.get_ident()}
            end = time.monotonic() + seconds
            while time.monotonic() < end:
                self._sample_once(me)
                time.sleep(self.interval)
            return self._report(seconds, top)
        finally:
            self._busy = False

    def start(self) -> None:
        """Begin open-ended background sampling (the
        /debug/pprof/sample/start endpoint): a daemon thread samples
        every OTHER thread until stop(). One session at a time."""
        with self._lock:
            if self._thread is not None or self._busy:
                raise RuntimeError("sampling profiler already running")
            self._reset()
            self._stop = threading.Event()
            self._started_at = time.monotonic()

            def run(stop=self._stop):
                skip = {threading.get_ident()}
                while not stop.is_set():
                    self._sample_once(skip)
                    stop.wait(self.interval)

            self._thread = threading.Thread(
                target=run, daemon=True, name="sampling-profiler")
            self._thread.start()

    def stop(self, top: int = 30) -> str:
        """End the background session and return its report."""
        with self._lock:
            if self._thread is None:
                raise RuntimeError("sampling profiler not running")
            self._stop.set()
            self._thread.join(timeout=5.0)
            self._thread = None
            self._stop = None
            return self._report(time.monotonic() - self._started_at, top)


class Tracer:
    """Span recorder with Chrome-trace export.

    Bounded ring of spans; thread-safe; zero overhead when disabled.
    """

    def __init__(self, max_spans: int = 100_000,
                 clock=time.perf_counter) -> None:
        self.max_spans = max_spans
        self.clock = clock
        self.enabled = True
        self._lock = threading.Lock()
        #: ring of (name, thread id, start_us, duration_us, args) — the
        #: newest max_spans survive (an operator debugging a current
        #: stall needs the RECENT activity, not warm-up)
        self._spans: list[tuple] = []
        self._next = 0
        #: external span sources (sidecar solves, followers, the farm)
        #: get stable SYNTHETIC track ids so their spans never
        #: interleave with host threads on one Chrome-trace track.
        #: Small ids are safe: host tids are pthread pointers.
        self._tracks: dict[str, int] = {}
        self._track_meta: dict[str, dict] = {}
        self._next_track = 2

    @contextmanager
    def span(self, name: str, **args):
        if not self.enabled:
            yield
            return
        t0 = self.clock()
        try:
            yield
        finally:
            dur = self.clock() - t0
            self._push((name, threading.get_ident(),
                        int(t0 * 1e6), int(dur * 1e6), args or None))

    def track(self, source: str, **meta) -> int:
        """Stable synthetic track id for an external span source
        (``"sidecar:tenant-a"``, ``"farm"``, ``"follower:1"``).
        ``meta`` (process/tenant tags) accumulates onto the track and
        exports as Chrome thread_name metadata."""
        with self._lock:
            tid = self._tracks.get(source)
            if tid is None:
                tid = self._tracks[source] = self._next_track
                self._next_track += 1
            if meta:
                self._track_meta.setdefault(source, {}).update(meta)
            return tid

    def add_span(self, name: str, ts_us: int, dur_us: int,
                 tid: Optional[int] = None,
                 source: Optional[str] = None, **args) -> None:
        """Record an externally-timed span (e.g. a sidecar solve whose
        timing arrived over the wire) into the same ring, so host and
        remote activity export as one Chrome-trace timeline. Pass
        ``source`` for external spans — they land on that source's own
        synthetic track instead of the CALLER's thread track (merged
        remote spans used to interleave with host spans)."""
        if not self.enabled:
            return
        if tid is None:
            tid = (self.track(source) if source is not None
                   else threading.get_ident())
        self._push((name, tid, int(ts_us), int(dur_us), args or None))

    def _push(self, entry: tuple) -> None:
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(entry)
            else:
                self._spans[self._next % self.max_spans] = entry
            self._next += 1

    def spans(self) -> list[tuple]:
        with self._lock:
            if len(self._spans) < self.max_spans:
                return list(self._spans)
            cut = self._next % self.max_spans
            return self._spans[cut:] + self._spans[:cut]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._next = 0

    def durations_ms(self, name: str) -> list[float]:
        return [dur / 1000 for (n, _, _, dur, _) in self.spans()
                if n == name]

    def chrome_trace(self, spans: Optional[list] = None) -> str:
        """Chrome-trace JSON ('X' complete events) — loadable in
        chrome://tracing or Perfetto alongside a JAX device trace.
        Synthetic source tracks lead with 'M' thread_name metadata so
        the timeline labels them by source + tenant/process tags."""
        with self._lock:
            tracks = sorted(self._tracks.items(), key=lambda kv: kv[1])
            meta = {s: dict(m) for s, m in self._track_meta.items()}
        events = []
        for src, tid in tracks:
            args = {"name": src}
            args.update(meta.get(src, {}))
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid, "args": args})
        for name, tid, ts, dur, args in (self.spans() if spans is None
                                         else spans):
            ev = {"name": name, "ph": "X", "pid": 1, "tid": tid,
                  "ts": ts, "dur": dur, "cat": "scheduler"}
            if args:
                ev["args"] = args
            events.append(ev)
        return json.dumps({"traceEvents": events,
                           "displayTimeUnit": "ms"})


class DebugServer:
    """HTTP debug endpoints (the pprofBindAddress analog):

    - ``GET /debug/pprof/profile?seconds=S`` — profile the process for
      S seconds, return the sampling report;
    - ``GET /debug/pprof/sample/start`` / ``.../sample/stop`` — the
      open-ended analog: start background sampling now, fetch the
      report whenever the incident is over (no fixed window up front);
    - ``GET /debug/trace`` — the tracer's Chrome-trace JSON;
    - ``GET /debug/trace/clear`` — reset the span ring.
    """

    def __init__(self, tracer: Optional[Tracer] = None,
                 port: int = 0) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import parse_qs, urlparse

        self.tracer = tracer
        sampler = SamplingProfiler()

        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _reply(self, code: int, body: str,
                       ctype: str = "text/plain") -> None:
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self) -> None:
                url = urlparse(self.path)
                if url.path == "/debug/pprof/profile":
                    qs = parse_qs(url.query)
                    try:
                        seconds = float(qs.get("seconds", ["1"])[0])
                    except ValueError:
                        self._reply(400, "seconds must be a number")
                        return
                    if not 0 < seconds <= 60:
                        self._reply(400, "seconds must be in (0, 60]")
                        return
                    # sampling profiler: sees every thread's stack, not
                    # just this handler thread (cProfile would not)
                    try:
                        self._reply(200, sampler.sample_for(seconds))
                    except RuntimeError as e:
                        self._reply(409, str(e))
                elif url.path == "/debug/pprof/sample/start":
                    try:
                        sampler.start()
                    except RuntimeError as e:
                        self._reply(409, str(e))
                    else:
                        self._reply(200, "sampling started")
                elif url.path == "/debug/pprof/sample/stop":
                    try:
                        self._reply(200, sampler.stop())
                    except RuntimeError as e:
                        self._reply(409, str(e))
                elif url.path == "/debug/trace":
                    if outer.tracer is None:
                        self._reply(404, "no tracer attached")
                    else:
                        self._reply(200, outer.tracer.chrome_trace(),
                                    "application/json")
                elif url.path == "/debug/trace/clear":
                    if outer.tracer is not None:
                        outer.tracer.clear()
                    self._reply(200, "ok")
                else:
                    self._reply(404, "not found")

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def attach_to_scheduler(scheduler, tracer: Tracer) -> None:
    """Make ``tracer`` a sink of the program's spans (obs/spans.py) and
    publish it on the scheduler. Nothing is patched: the scheduler, the
    engine and the store open their own spans (``quiet`` > ``route`` >
    ``solver_drain`` > ``export`` / ``solve`` / ``apply`` ...,
    ``schedule`` > ``snapshot`` / ``nominate`` / ``entries`` /
    ``flush``), and while a sink is registered each one lands in the
    tracer's ring with its cycle id, beside the sidecar's and the farm's
    spans: one merged timeline. The sink is held weakly: dropping the
    tracer (and the scheduler that publishes it) switches the record
    off again."""
    from kueue_oss_tpu.obs import spans

    scheduler.tracer = tracer
    spans.add_sink(tracer)
