"""95th percentile, over the answered requests, of the engine's dispatch of
each one's batch (``RequestContext.dispatch_s``: flush start to the launch
call's return: batch take, stacking, bucket padding, the launch)."""
import numpy as np

from chipbench import contexts


def read(name, run):
    values = contexts.answered(run, "dispatch_s")
    return None if values is None else float(np.percentile(values, 95)) * 1e3
