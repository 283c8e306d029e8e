"""Opt-in ``jax.profiler`` capture of a window of decode steps.

Kernel-level traces of the fused decode megakernel are one flag away:
``launch/serve.py --profile-steps N --profile-dir DIR`` arms a
:class:`StepProfiler` on the scheduler, which starts a ``jax.profiler``
trace right before decode step ``skip`` (default 1 — step 0 is the jit
compile and would bury the steady state under lowering noise) and stops
it ``N`` steps later.  The capture is TensorBoard/Perfetto-compatible
(``tensorboard --logdir DIR`` or load the ``.trace.json.gz`` into
ui.perfetto.dev).

The profiler is pure host-side control flow around the already-compiled
step — arming it cannot recompile or perturb token streams.  A capture
that was asked for and cannot start or stop raises: a run that was meant
to be traced and silently was not would be read as a traced one.
"""

from __future__ import annotations

import sys
from typing import Optional

import jax


class StepProfiler:
    """Capture ``[skip, skip + steps)`` decode steps into ``out_dir``."""

    def __init__(self, steps: int, out_dir: str, *, skip: int = 1):
        if steps < 1:
            raise ValueError(f"profile window must be >= 1 step, got {steps}")
        self.steps = steps
        self.out_dir = out_dir
        self.skip = skip
        self._seen = 0
        self._state = "armed"  # armed -> tracing -> done

    @property
    def tracing(self) -> bool:
        return self._state == "tracing"

    @property
    def done(self) -> bool:
        return self._state == "done"

    def tick(self) -> None:
        """Call once per completed decode step (after device sync)."""
        if self.done:
            return
        if self._state == "armed" and self._seen == self.skip:
            jax.profiler.start_trace(self.out_dir)
            self._state = "tracing"
            self._t0_step = self._seen
        self._seen += 1
        if self._state == "tracing" and self._seen - self._t0_step >= self.steps:
            self.stop()

    def stop(self) -> Optional[str]:
        """Stop an in-flight capture (also called on scheduler drain)."""
        if self._state != "tracing":
            return None
        jax.profiler.stop_trace()
        self._state = "done"
        print(f"[obs] captured {self.steps} decode steps to {self.out_dir} "
              "(tensorboard --logdir, or open the .trace.json.gz in "
              "ui.perfetto.dev)", file=sys.stderr)
        return self.out_dir
