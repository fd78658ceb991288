"""The yardstick's arithmetic: chip peaks, model FLOPs per token, and the
bytes a compiled kernel call moves, read from its instruction text."""
from __future__ import annotations

import json
import pathlib
import re

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
          "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
          "f32": 4, "s64": 8, "u64": 8, "f64": 8, "s4": 0.5, "u4": 0.5}
_ARRAY = re.compile(r"\b(" + "|".join(sorted(_BYTES, key=len, reverse=True))
                    + r")\[([0-9,]*)\]")


def peaks(device_kind: str) -> dict:
    """The peak row of `device_kind`; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def array_bytes(types: str) -> float:
    """Total bytes of every array type (e.g. 'f32[8,128]{1,0}') in a string."""
    total = 0.0
    for dtype, dims in _ARRAY.findall(types):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _BYTES[dtype]
    return total


def _close(text: str, i: int) -> int:
    """Index just past the bracket group that opens at text[i]."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "([{":
            depth += 1
        elif text[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def parse_instruction(text: str) -> dict:
    """Split an HLO instruction ('%name = type opcode(operands), attrs')
    into name, result type, opcode and operand text."""
    lhs, _, rest = text.partition(" = ")
    name = lhs.strip().lstrip("%")
    end = _close(rest, 0) if rest.startswith("(") else rest.find(" ")
    result = rest[:end]
    tail = rest[end:].lstrip()
    paren = tail.find("(")
    opcode = tail[:paren] if paren >= 0 else tail
    operands = tail[paren:_close(tail, paren)] if paren >= 0 else ""
    return {"name": name, "result": result, "opcode": opcode.strip(),
            "operands": operands, "attrs": tail[paren + len(operands):]}


def stable_name(inst_name: str) -> str:
    """'evict_argmin_pallas.7' -> 'evict_argmin_pallas'."""
    return re.sub(r"(\.\d+)+$", "", inst_name)


def call_bytes(text: str) -> float:
    """Bytes a call reads and writes at least once: its operands' and
    results' sizes, as the compiled instruction states them."""
    inst = parse_instruction(text)
    return array_bytes(inst["result"]) + array_bytes(inst["operands"])


def decoder_flops_per_token(config: dict, keys: int, logits: bool) -> float:
    """Model FLOPs of one token through a dense GQA decoder: every matmul
    (2 per multiply-add) plus attention over `keys` positions, and the
    vocabulary projection where the token's logits are computed."""
    d, L = config["hidden_size"], config["num_hidden_layers"]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    ff, V = config["intermediate_size"], config["vocab_size"]
    hd = d // H
    proj = d * H * hd + 2 * d * G * hd + H * hd * d + 3 * d * ff
    attn = 2 * H * hd * keys          # q.k and p.v, multiply-adds
    return 2.0 * L * (proj + attn) + (2.0 * d * V if logits else 0.0)
