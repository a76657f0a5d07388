"""executor_roofline: least time of the delivered work over the executor's
device time in the trace, in percent.

Least time: every request claimed in the traced part of the window must
read its inputs and write its outputs once, (n_inputs + n_outputs) x 4 B
x length, at the chip's peak HBM bandwidth.  HBM is the bound: the paper's kernels do under
3 operations per byte (gradient 11 / 24 = 0.46, poly6 44 / 16 = 2.75),
far below the v5e's ridge of 197 TFLOP/s over 819 GB/s, about 240.  The
count depends on the requests alone, so a change that removes padding or
swaps the executor leaves the yardstick where it was.

Executor time: the device time of the programs named below, in the
trace's XLA Modules line.  If the program renames them, the metric reads
nothing until a benchmark change points it at the new names.
"""

EXECUTOR_MODULES = ("vm_exec_multi", "tmfu_pipeline_rf_multi")
BYTES_PER_ELEMENT = 4


def read(run):
    if run.trace is None:
        return None
    device_s = sum(s for name, s in run.trace["module_seconds"].items()
                   if any(m in name for m in EXECUTOR_MODULES))
    if device_s <= 0:
        return None
    nbytes = request_bytes(run.specs, [(r.kernel, r.length)
                                       for r in run.delivered_in_trace()])
    return 100.0 * nbytes / run.peak("hbm_bytes_per_s") / device_s


def request_bytes(specs, requests) -> int:
    """Bytes the ``(kernel, length)`` requests must move at the least:
    each input read once and each output written once."""
    return sum((len(specs[k].inputs) + len(specs[k].outputs))
               * BYTES_PER_ELEMENT * n for k, n in requests)
