"""Least time for a lookup's algorithm-free traffic (16 bytes per key,
one read of the 8n-byte id table per call: chipbench.flops.
ring_lookup_bytes) at the chip's HBM bandwidth, over the device time of
the lookup programs in the traced window: the XLA modules
``jit_ring_lookup64`` (flat scan) and ``jit_ring_lookup_bucketed``
(bucketed kernel with the row gather that feeds it)."""
from chipbench import flops, peaks


def read(run):
    tr, lay = run.trace, run.layer
    if tr is None or not tr.chips or not lay.get("traced_calls"):
        return None
    t = tr.modules_matching(lambda n: n.startswith("jit_ring_lookup"))
    if t <= 0:
        return None
    # linear in both, so the sum over the traced calls is one call's form
    nbytes = flops.ring_lookup_bytes(lay["traced_keys"], lay["traced_table_ids"])
    bw = peaks.peaks_for(lay["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / t
