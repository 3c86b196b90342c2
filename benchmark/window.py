"""The measured window: a wall-clock budget the driver polls between
operations, with a steady sub-window traced by the JAX profiler in a
``--trace 1`` run, and a counter snapshot at each of its four edges."""

from __future__ import annotations

import shutil
import time
from pathlib import Path

#: a traced sub-window starts this far into the window, or a quarter in
TRACE_START_S = 2.0


class Window:
    def __init__(
        self, seconds: float, trace_dir: Path | None, trace_seconds: float
    ):
        """``trace_dir`` None: no profiler.  ``trace_seconds``: length
        of the traced sub-window, cut to half the window."""
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self._trace_from = min(TRACE_START_S, self.seconds / 4.0)
        self._trace_len = min(float(trace_seconds), self.seconds / 2.0)
        self._tracing = False
        self.t_open = self.t_close = None
        self.t_trace = [None, None]
        self.snap: dict = {}

    def _snapshot(self, edge: str) -> None:
        from holo_tpu import telemetry

        self.snap[edge] = telemetry.snapshot("holo_")

    def open(self) -> None:
        self._snapshot("open")
        self.t_open = time.perf_counter()

    def tick(self) -> bool:
        """True while the window lasts.  Called between operations, so
        the trace starts and stops on an operation's edge."""
        now = time.perf_counter()
        if self.trace_dir is not None:
            if self.t_trace[0] is None:
                if now - self.t_open >= self._trace_from:
                    self._start_trace()
            elif self._tracing and now - self.t_trace[0] >= self._trace_len:
                self._stop_trace()
        return now - self.t_open < self.seconds

    def close(self) -> None:
        if self._tracing:
            self._stop_trace()
        self.t_close = time.perf_counter()
        self._snapshot("close")

    # -- what moved between two edges (default: over the window)

    def _children(self, edge: str, family: str, label: str) -> list:
        return [
            v for k, v in self.snap[edge].items()
            if k.split("{", 1)[0] == family and label in k
        ]

    def counter_delta(
        self, family: str, label: str = "", edges=("open", "close")
    ) -> float:
        """Move of a counter family's children whose label string
        contains ``label``.  A family never touched reads 0."""
        first, last = (sum(self._children(e, family, label)) for e in edges)
        return last - first

    def histogram_delta(
        self, family: str, label: str = "", edges=("open", "close")
    ) -> tuple[int, float]:
        """``(observations, their sum)`` a histogram family gained."""
        first, last = (
            (sum(c["count"] for c in ch), sum(c["sum"] for c in ch))
            for ch in (self._children(e, family, label) for e in edges)
        )
        return last[0] - first[0], last[1] - first[1]

    @property
    def wall(self) -> float:
        return self.t_close - self.t_open

    def _start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        self._snapshot("trace_open")
        # Host spans (TraceAnnotation, the runtime's own) but no Python
        # tracer: it would slow the host whose gaps are being measured.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(
            str(self.trace_dir), profiler_options=options
        )
        self._tracing = True
        self.t_trace[0] = time.perf_counter()

    def _stop_trace(self) -> None:
        import jax

        self.t_trace[1] = time.perf_counter()
        jax.profiler.stop_trace()
        self._tracing = False
        self._snapshot("trace_close")

    @property
    def trace_wall(self) -> float | None:
        if None in self.t_trace:
            return None
        return self.t_trace[1] - self.t_trace[0]

    def trace_file(self) -> Path | None:
        """The ``.xplane.pb`` the profiler wrote, if it wrote one."""
        if self.trace_dir is None:
            return None
        found = sorted(self.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None
