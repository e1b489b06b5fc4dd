"""paddle_tpu: a TPU-native deep-learning framework.

A ground-up rebuild of the capabilities of 2017-era PaddlePaddle
(reference: /root/reference) designed TPU-first:

- compute path: JAX/XLA traced functions, pjit/shard_map over a
  ``jax.sharding.Mesh``, Pallas kernels where XLA fusion falls short
  (replaces the reference's paddle/cuda + paddle/math CUDA stack,
  reference: paddle/math/Matrix.h:79, paddle/cuda/include/hl_matrix.h);
- layer/op library as pure functions + a light module system
  (replaces paddle/gserver/layers, reference: gserver/layers/Layer.h:62);
- event-driven trainer with evaluators, checkpointing, gradient checking
  (replaces paddle/trainer, reference: trainer/Trainer.cpp:265);
- mesh parallelism over ICI/DCN collectives (replaces
  paddle/pserver + MultiGradientMachine, reference:
  gserver/gradientmachines/MultiGradientMachine.h:44);
- padding-free variable-length sequence training + beam-search decoding
  (replaces RecurrentGradientMachine, reference:
  gserver/gradientmachines/RecurrentGradientMachine.cpp:530).

The import times itself: the row `import.paddle_tpu` of
`obs.trace.default_timeline()` runs from this file's first statement to
its last, so it covers what this one `import` pulled in. Where jax was
loaded before (the chip benchmark) that is the package's own modules;
at the CLI jax loads inside it and is in the row.
"""

import time as _time
_import_t0_ns = _time.perf_counter_ns()

__version__ = "0.1.0"

from paddle_tpu import core
from paddle_tpu import ops
from paddle_tpu import nn
from paddle_tpu import optim
from paddle_tpu import data
from paddle_tpu import train
from paddle_tpu import parallel
from paddle_tpu import models
from paddle_tpu import metrics

from paddle_tpu.obs.trace import default_timeline as _default_timeline
_default_timeline().add("import.paddle_tpu", _import_t0_ns,
                        _time.perf_counter_ns())
