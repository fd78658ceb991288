"""What a driver's window hands back to the harness."""
from __future__ import annotations

import dataclasses
import shutil

from jax import monitoring

from .profile import Capture, reduce_trace


@dataclasses.dataclass
class Window:
    end_to_end: dict            # host-clock metrics of the window
    attempted: int
    failed: int
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    series: dict = dataclasses.field(default_factory=dict)
    trace: object = None        # profile.Reduction of the traced part
    notes: list = dataclasses.field(default_factory=list)
    outputs: object = None      # what the timed path produced, for check()


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while active."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0
        self.active = False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and name in self._EVENTS:
            self.count += 1


def traced(directory, fn):
    """Run fn() under the profiler; (fn's result, Reduction)."""
    with Capture(directory) as cap:
        out = fn()
    try:
        return out, reduce_trace(cap.path)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
