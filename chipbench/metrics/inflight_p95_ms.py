"""95th percentile, over the answered requests, of each one's batch's time in
flight (``RequestContext.inflight_s``: the launch call's return to the
harvest: the device queue ahead of it, its own compute, any harvest lag)."""
import numpy as np

from chipbench import contexts


def read(name, run):
    values = contexts.answered(run, "inflight_s")
    return None if values is None else float(np.percentile(values, 95)) * 1e3
