"""Share of the answered requests whose batch's launch had to re-stage its
plan (``RequestContext.restage_s > 0``), in ``%``.  Nothing to read from a
program without the stamp."""
from chipbench import contexts


def read(name, run):
    values = contexts.answered(run, "restage_s")
    return None if values is None else 100.0 * float((values > 0).mean())
