"""HLO parsers: collective bytes (shapes, tuples, while-trip multiplication)
and the named scope of each instruction that runs on the device."""
from repro.launch.hlo_analysis import (_shape_bytes, _split_computations,
                                       analyze_collectives, scope_map)


def test_shape_bytes():
    assert _shape_bytes("f32[2,512,1024]") == 2 * 512 * 1024 * 4
    assert _shape_bytes("bf16[16]{0}") == 32
    assert _shape_bytes("(f32[8], bf16[8])") == 32 + 16
    assert _shape_bytes("pred[]") == 0 or _shape_bytes("pred[]") == 1


_HLO = """
HloModule test

%body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %p = (s32[], f32[128]) parameter(0)
  %ar = f32[128]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

%cond (p2: (s32[], f32[128])) -> pred[] {
  %p2 = (s32[], f32[128]) parameter(0)
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i2, %c), direction=LT
}

ENTRY %main (a: f32[256]) -> f32[256] {
  %a = f32[256]{0} parameter(0)
  %ag = f32[256]{0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[128]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[256]{0} add(%ag, %ag)
}
"""


def test_while_trip_multiplication():
    cs = analyze_collectives(_HLO)
    # all-gather once at entry: 256*4 bytes
    assert cs.bytes_by_kind["all-gather"] == 256 * 4
    # all-reduce inside the while body: 128*4 bytes * 7 trips
    assert cs.bytes_by_kind["all-reduce"] == 128 * 4 * 7
    assert cs.count_by_kind["all-reduce"] == 7


def test_split_handles_tuple_params():
    comps = _split_computations(_HLO)
    assert "body" in comps and "cond" in comps and "main" in comps


def test_instruction_name_with_opcode_substring():
    hlo = """
ENTRY %main (a: f32[4]) -> f32[4] {
  %all-gather.61 = f32[4]{0} all-gather(%a), dimensions={0}
  ROOT %r = f32[4]{0} add(%all-gather.61, %all-gather.61)
}
"""
    cs = analyze_collectives(hlo)
    assert cs.count_by_kind["all-gather"] == 1
    assert cs.bytes_by_kind["all-gather"] == 16

_SCOPED = """
HloModule scoped

%fused (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %inner = f32[8]{0} negate(%p0), metadata={op_name="jit(f)/a/neg"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %s = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/while/body/a/neg"}
  %cp = f32[8]{1:T(8)} copy(%s)
  %v = f32[8]{0} add(%cp, %x), metadata={op_name="jit(f)/while/body/b/jit(k)/add"}
  %i = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %n = s32[] add(%i, %one), metadata={op_name="jit(f)/while/body/add"}
  ROOT %t = (s32[], f32[8]) tuple(%n, %v)
}

%cond (q: (s32[], f32[8])) -> pred[] {
  %q = (s32[], f32[8]) parameter(0)
  %j = s32[] get-tuple-element(%q), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%j, %c), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8]) tuple(%z, %a)
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""


def test_scope_map_reads_loop_instructions_and_inherits_scopes():
    m = scope_map(_SCOPED, ("a", "b"))
    # a fusion takes its own op_name's scope; its fused body is not read
    assert "s" in m["a"] and "inner" not in sum(m.values(), [])
    # the layout copy has no op_name: its only user (in `b`) decides
    assert "cp" in m["b"] and "v" in m["b"]
    # the loop counter names no scope; nor do the parameter, the
    # condition's compare or the entry's own instructions
    assert {"n", "lt", "p", "w", "a"} <= set(m["unscoped"])
    # x has two users, so its first operand, the loop parameter, decides
    assert "x" in m["unscoped"]
    names = sum(m.values(), [])
    assert len(names) == len(set(names)) == 9 + 4 + 5   # body, cond, entry
