"""Numeric-debug hook.

feenableexcept FP trapping (reference: trainer/TrainerMain.cpp:49) ->
jax debug_nans. The reference's profiler hooks (hl_profiler_start/end,
cuda/include/hl_cuda.h:338-343) and per-layer named timers
(gserver/gradientmachines/NeuralNetwork.cpp:260) are jax's own
`jax.profiler.start_trace/stop_trace/trace` and `jax.named_scope`,
called directly; the host side of a step is timed by
`paddle_tpu.obs.trace.Timeline`.
"""

from __future__ import annotations

import jax


def debug_nans(enable: bool = True):
    """Trap NaNs at op granularity (the FP-exception-trap analog)."""
    jax.config.update("jax_debug_nans", enable)
