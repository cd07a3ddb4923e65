"""Mean columns per launched batch in the window: the engine's own
``serving.columns`` over ``serving.batches`` counters."""


def read(name, run):
    return run.window.get("batch_k_mean")
