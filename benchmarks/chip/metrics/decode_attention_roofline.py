"""Least time for the KV bytes that each decoded row's real length
requires (chipbench.flops.decode_attention_bytes, at the chip's HBM
bandwidth, or its FLOPs at peak if larger), over the device time of the
``decode_attention`` kernel's events in the traced window."""
from chipbench import flops, peaks


def read(run):
    tr, lay = run.trace, run.layer
    if tr is None or not tr.chips or not lay.get("decode_rows"):
        return None
    t = tr.ops_matching(lambda n: n == "decode_attention")
    if t <= 0:
        return None
    cfg = lay["config"]
    pk = peaks.peaks_for(lay["device_kind"])
    need = sum(flops.roofline_seconds(flops.decode_attention_flops(cfg, n),
                                      flops.decode_attention_bytes(cfg, n), pk)
               for rows in lay["decode_rows"] for n in rows)
    return 100.0 * need / t
