"""The yardstick's arithmetic: peaks, FLOPs per token, bytes of a call."""
import pytest

import cells  # noqa: F401  (puts the harness on sys.path)
from harness import counts

KERNEL = ('%evict_argmin_pallas.7 = (s32[6,4,4,1,128]{4,3,2,1,0:T(1,128)S(1)},'
          ' f32[6,4,4,1,128]{4,3,2,1,0:T(1,128)S(1)}) custom-call('
          'f32[6,4,4,512,128]{4,3,2,1,0:T(8,128)S(1)} %reshape.418, '
          's32[512,128]{1,0:T(8,128)S(1)} %bitcast.83, '
          's32[6,4,4,512,128]{4,3,2,1,0:T(8,128)} %reshape.419), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{f32[6,4,4,512,128]{4,3,2,1,0}, s32[512,128]{1,0}, '
          's32[6,4,4,512,128]{4,3,2,1,0}}')


def test_peaks_are_keyed_by_device_kind():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_call_bytes_reads_operands_and_results_not_attributes():
    inst = counts.parse_instruction(KERNEL)
    assert inst["name"] == "evict_argmin_pallas.7"
    assert inst["opcode"] == "custom-call"
    assert "tpu_custom_call" in inst["attrs"]
    operands = 96 * 512 * 128 * 4 * 2 + 512 * 128 * 4
    results = 2 * 96 * 128 * 4
    # the layout constraints repeat the operand types; they are not counted
    assert counts.call_bytes(KERNEL) == operands + results


@pytest.mark.parametrize("text,want", [
    ("%copy.1 = f32[65536]{0:T(1024)} copy(f32[65536]{0:T(1024)} %a)",
     2 * 65536 * 4),
    ("%c = bf16[8,128]{1,0} convert(s8[8,128]{1,0} %x)", 8 * 128 * 3),
    ("%t = (pred[4]{0}, s32[]{:T(128)}) tuple(pred[4]{0} %p, s32[] %q)",
     2 * (4 + 4)),
])
def test_array_bytes_by_dtype(text, want):
    assert counts.call_bytes(text) == want


def test_stable_name_drops_instruction_numbers():
    assert counts.stable_name("evict_argmin_pallas.7") == "evict_argmin_pallas"
    assert counts.stable_name("fusion.1.2") == "fusion"


def test_decoder_flops_per_token_by_hand():
    cfg = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
           "num_key_value_heads": 1, "intermediate_size": 16,
           "vocab_size": 10}
    # per layer: q 8x8, k and v 8x4 each, o 8x8, three 8x16 ffn matrices
    proj = 64 + 2 * 32 + 64 + 3 * 128
    assert counts.decoder_flops_per_token(cfg, 0, False) == 2 * 2 * proj
    # attention over 5 keys: q.k and p.v, 2 heads of 4 dims, 2 layers
    attn = 2 * 2 * (2 * 2 * 4 * 5)
    assert counts.decoder_flops_per_token(cfg, 5, True) == \
        2 * 2 * proj + attn + 2 * 8 * 10
