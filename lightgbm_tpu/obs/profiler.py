"""Opt-in ``jax.profiler`` capture of a training-iteration window.

``telemetry_profile_iters=[k, n]`` captures iterations [k, k+n) into a
TensorBoard-loadable trace directory.  The window is driven by the GBDT
iteration loop (models/gbdt.py) through ``on_iter_begin``/``on_iter_end``
so the capture brackets exactly the requested iterations — including
their compile, if iteration k is the first of a new jitted shape.

The capture is something the user asked for by setting the parameter: a
``start_trace`` that fails raises out of the training loop rather than
letting the run finish without the trace it was started to produce.
"""

from __future__ import annotations

import atexit


class ProfilerWindow:
    """Capture iterations [start, start + count) with jax.profiler."""

    def __init__(self, start: int, count: int, logdir: str):
        self.start = int(start)
        self.count = max(int(count), 1)
        self.logdir = logdir
        self.active = False

    def on_iter_begin(self, it: int) -> None:
        if self.active or it != self.start:
            return
        import jax.profiler
        jax.profiler.start_trace(self.logdir)
        self.active = True
        # a crash inside the window must still flush the capture
        atexit.register(self.finish)
        from ..utils.log import Log
        Log.info(f"telemetry: jax.profiler capturing iterations "
                 f"[{self.start}, {self.start + self.count}) -> "
                 f"{self.logdir}")

    def on_iter_end(self, it: int) -> None:
        if self.active and it + 1 >= self.start + self.count:
            self.finish()

    def finish(self) -> None:
        if not self.active:
            return
        self.active = False
        try:
            import jax.profiler
            jax.profiler.stop_trace()
        except Exception as e:
            from ..utils.log import Log
            Log.warning(f"telemetry: jax.profiler stop failed ({e})")
