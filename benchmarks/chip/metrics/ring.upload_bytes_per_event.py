"""Bytes the routing table shipped to the device over the window
(``RingState.upload_bytes``), per membership event applied."""


def read(run):
    lay = run.layer
    if not lay.get("events"):
        return None
    return lay["upload_bytes"] / lay["events"]
