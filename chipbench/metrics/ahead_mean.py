"""Mean, over the answered requests, of the batches already in flight when
each one's batch was launched (``RequestContext.ahead``)."""
from chipbench import contexts


def read(name, run):
    values = contexts.answered(run, "ahead")
    return None if values is None else float(values.mean())
