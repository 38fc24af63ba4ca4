"""Start and stop the profiler around a slice of the window, then load and
reduce what it wrote. Python-level tracing is off (it slows the host loop);
host ``TraceAnnotation`` spans stay on."""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Dict, Optional

from . import xplane


class TraceSlice:
    def __init__(self, directory: str) -> None:
        self.directory = directory
        self.active = False
        self.done = False
        self.t_start = self.t_stop = 0.0  # host clock (perf_counter)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.t_start = time.perf_counter()
        self.active = True

    def stop(self) -> None:
        import jax

        if self.active:
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def reduce(self) -> Optional[Dict[str, Any]]:
        if not self.done:
            return None
        raw = xplane.load(xplane.find_xplane(self.directory))
        # the traced slice: from the first to the last benchmark span in it
        spans = raw["spans"]
        window = (spans[0][1], max(s[2] for s in spans)) if spans else None
        return {"raw": raw, "reduced": xplane.reduce(raw, window)}
