"""Host seconds of one phase of the cell's admission, as the registry
recorded it in ``MatrixPlan.phases_s``: ``admit_phase_s.key`` (content
hash), ``.tiles`` (tile build), ``.stage`` (device staging), ``.quality``
(partition quality).  Nothing to read where the driver admitted no plan or
the plan records no phases."""


def read(name, run):
    phases = getattr(run.state.get("plan"), "phases_s", None)
    if not phases:
        return None
    return phases.get(name.split(".", 1)[1])
