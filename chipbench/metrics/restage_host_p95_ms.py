"""95th percentile, over the answered requests whose batch's launch had to
re-stage its plan, of the host seconds that re-stage took
(``RequestContext.restage_s``, timed around the registry's
``serve.restage`` span).  Host time only: the staging call returns once
the transfer is issued, and the transfer itself runs on past it.  Nothing
to read from a program without the stamp, or where no request
re-staged."""
import numpy as np

from chipbench import contexts


def read(name, run):
    values = contexts.answered(run, "restage_s")
    if values is None or not (values > 0).any():
        return None
    return float(np.percentile(values[values > 0], 95)) * 1e3
