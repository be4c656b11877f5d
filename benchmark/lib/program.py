"""What the program under test counted about itself, for the per-layer
readers whose ``source`` is ``program_counter`` or ``program_span``."""

STEP_FUNCTION = "hvd_train_step"  # the name dp.make_train_step jits under


def snapshot() -> dict:
    """``hvd.obs.snapshot()``: counters, gauges and histogram summaries of
    this process. A program older than a series simply lacks it."""
    import horovod_tpu as hvd

    return hvd.obs.snapshot()


def step_builds() -> dict:
    """``{"traces", "lowerings", "compiles", "trace_s", "lower_s",
    "compile_s"}`` of the train step so far, as the program booked them
    (``build.<key>.hvd_train_step``); empty where it does not count."""
    booked = snapshot()
    series = {**booked["counters"], **booked["gauges"]}
    prefix, suffix = "build.", "." + STEP_FUNCTION
    return {
        name[len(prefix):-len(suffix)]: value
        for name, value in series.items()
        if name.startswith(prefix) and name.endswith(suffix)
    }
