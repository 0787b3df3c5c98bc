"""The measured window, and the profiler around it on traced runs."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path
from typing import Optional

from . import trace as tr


class Window:
    """``open()`` starts the clock (after starting the profiler on a traced
    run, so that its start-up is not timed); ``close()`` stops it;
    ``finish()`` stops the profiler and reduces its trace."""

    def __init__(self, trace_dir: Optional[Path]):
        self.trace_dir = trace_dir
        self.t_open = self.t_close = None
        self._span = None

    def open(self) -> float:
        if self.trace_dir is not None:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=tr.profile_options())
            self._span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
            self._span.__enter__()
        self.t_open = time.perf_counter()
        return self.t_open

    def close(self) -> float:
        self.t_close = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        return self.t_close

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def finish(self, save: Optional[Path] = None, all_lines: bool = False):
        """The window's ``trace.Trace`` (None on untimed runs). ``save``
        keeps the recorded trace as JSON (every device line with
        ``all_lines``); the profiler's files go."""
        if self.trace_dir is None:
            return None
        import jax

        jax.profiler.stop_trace()
        recorded = tr.record(str(self.trace_dir),
                             keep_lines=None if all_lines else [tr.OPS_LINE])
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        if save is not None:
            save.parent.mkdir(parents=True, exist_ok=True)
            save.write_text(json.dumps(recorded))
        return tr.Trace(recorded)
