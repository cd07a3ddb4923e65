"""Share of its roofline the HBP Pallas kernels reach (``%``), from the trace.

The least time is ``max(flops / peak FLOP/s, bytes / peak HBM bytes/s)`` of
the CSR-equivalent work the window completed (``work.csr_work``: requests,
steps or PageRank iterations, never the padded tiles), over the device time
of the kernels' events in the traced window.  Nothing to read without a
trace or without kernel events.
"""
from chipbench import work


def read(name, run):
    if run.trace is None or run.trace["kernel_s"] <= 0:
        return None
    share, _ = work.roofline(run.window["work_flops"], run.window["work_bytes"],
                             run.trace["kernel_s"], work.peaks(run.device_kind))
    return share
