"""The sweep kernels' share of their roofline: least time for the cell
updates the Pallas kernels swept in the traced window (``work.py`` from
``peaks.json``) over the summed device time of the ``tpu_custom_call`` op
events inside the step modules.  Nothing to read (no kernel ran, no such event):
the metric is left out, never 0."""

from benchmark.harness import work
from benchmark.layer_metrics.step_device_ms import step_module_names


def kernel_seconds(reduced, ctx):
    return sum(reduced["kernel_s"].get(m, 0.0)
               for m in step_module_names(reduced, ctx))


def read(reduced, spans, counts, ctx):
    sec = kernel_seconds(reduced, ctx)
    n = counts.get("kernel_cell_updates", 0)
    if sec <= 0 or not n:
        return None
    least, _ = work.least_time_s(n, ctx["peak"])
    return 100.0 * least / sec
