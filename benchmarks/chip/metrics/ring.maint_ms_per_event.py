"""Host time inside ``RingState.apply_events`` over the window, per
membership event applied (span in the benchmark around each call)."""


def read(run):
    lay = run.layer
    if not lay.get("events"):
        return None
    return 1e3 * lay["apply_s"] / lay["events"]
