"""Device time of the admission prefill-chunk programs (XLA module
``jit_prefill_chunk``) over the device's busy time in the traced
window."""


def read(run):
    tr = run.trace
    if tr is None or tr.busy_s <= 0:
        return None
    pre = tr.modules_matching(lambda n: n == "jit_prefill_chunk")
    return 100.0 * pre / (tr.busy_s * tr.chips)
