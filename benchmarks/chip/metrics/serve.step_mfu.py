"""Required FLOPs of the tokens prefilled and decoded in the traced
window, over the window times the chip's bf16 peak.  FLOPs are counted
from shapes (chipbench.flops): causal attention over each token's real
context, the output head only where a token is needed; padding and
recomputation are not counted."""
from chipbench import flops, peaks


def read(run):
    tr, lay = run.trace, run.layer
    if tr is None or not tr.chips or tr.window_s <= 0 or not lay.get("decode_rows"):
        return None
    cfg = lay["config"]
    n = sum(flops.decode_flops(cfg, length)
            for rows in lay["decode_rows"] for length in rows)
    n += sum(flops.prefill_flops(cfg, 0, p) for p in lay["prefill_tokens"])
    peak = peaks.peaks_for(lay["device_kind"])["bf16_flops_per_s"]
    return 100.0 * n / (tr.window_s * tr.chips * peak)
