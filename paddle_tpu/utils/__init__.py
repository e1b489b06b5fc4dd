"""Support utilities: numeric debugging, plots, diagrams, importers."""

from paddle_tpu.utils.profiler import debug_nans
from paddle_tpu.utils.plot import CostCurve
from paddle_tpu.utils.diagram import model_to_dot
