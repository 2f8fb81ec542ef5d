"""FLOP and byte counts behind the MFU and roofline metrics, and the
peaks table, against hand-computed values."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import flops, peaks  # noqa: E402

QWEN = json.loads((HERE / "configs" / "qwen2.5-3b.json").read_text())


def test_qwen_parameter_count():
    # per layer: q 2048*2048 + k,v 2*2048*256 + o 2048*2048
    # + MLP 3*2048*11008 + biases 2048+2*256 + norms 2*2048
    per_layer = 9_437_184 + 67_633_152 + 2_560 + 4_096
    want = 36 * per_layer + 151_936 * 2048 + 2048   # tied embedding once
    assert flops.param_count(QWEN) == want == 3_085_938_688
    untied = dict(QWEN, tie_word_embeddings=False)
    assert flops.param_count(untied) == want + 151_936 * 2048


def test_token_and_prefill_flops():
    mm = 36 * 77_070_336                 # matmul weights per token
    attn = 4 * 16 * 128 * 36             # per attended position
    head = 2048 * 151_936
    assert flops.decode_flops(QWEN, 100) == 2 * mm + attn * 100 + 2 * head
    # three prompt tokens attend over 1, 2, 3 positions; one head
    assert flops.prefill_flops(QWEN, 0, 3) == \
        3 * 2 * mm + attn * (1 + 2 + 3) + 2 * head
    assert flops.prefill_flops(QWEN, 5, 5) == 0


def test_decode_attention_bytes():
    # K and V of 300 positions x 2 kv heads x 128 + q and out of 16 heads,
    # bf16, over 36 layers
    assert flops.decode_attention_bytes(QWEN, 300) == \
        36 * 2 * (2 * 300 * 2 * 128 + 2 * 16 * 128)


def test_ring_lookup_bytes_do_not_depend_on_the_kernel():
    assert flops.ring_lookup_bytes(1 << 20, 10**6) == 16 * (1 << 20) + 8 * 10**6
    assert flops.ring_lookup_bytes(1600, 10**6) == 16 * 1600 + 8 * 10**6


def test_roofline_seconds_takes_the_larger_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 0, pk) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, pk) == pytest.approx(1.0)
    assert flops.roofline_seconds(197e12, 2 * 819e9, pk) == pytest.approx(2.0)


def test_peaks_are_keyed_by_device_kind():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(ValueError):
        peaks.peaks_for("cpu")
