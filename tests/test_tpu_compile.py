"""Compile guards: the main-path Pallas kernels and the replay grid, compiled
by the TPU compiler for a described (not attached) v5e chip.

Interpret-mode tests cannot see what Mosaic refuses (unsupported reductions,
scalar stores to VMEM, unaligned blocks). These compile each kernel at
deployment sizes and the 96-cell grid with the real kernel inside it. The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one that runs this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import policies_jax
from repro.kernels import ops
from repro.kernels.interval_occupancy import (interval_occupancy_pallas,
                                              occupancy_feasible_pallas)


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [65_536, 1_048_576])
def test_evict_argmin_compiles(chip, n, monkeypatch):
    """Flat tables, laid out as tiles by `ops.evict_argmin` on the way in."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    _assert_kernel(jax.jit(ops.evict_argmin).lower(
        _spec(chip, (n,), jnp.float32), _spec(chip, (n,), jnp.int32),
        _spec(chip, (n,), jnp.bool_)))


def test_occupancy_feasible_compiles(chip):
    t = 262_144
    _assert_kernel(occupancy_feasible_pallas.lower(
        _spec(chip, (t,), jnp.float32), _spec(chip, (t,), jnp.float32),
        interpret=False))


def test_interval_occupancy_compiles(chip):
    _assert_kernel(interval_occupancy_pallas.lower(
        _spec(chip, (262_144,), jnp.float32), interpret=False))


@pytest.fixture(scope="module")
def grid(chip):
    """The 6 policies x 4 prices x 4 budgets program, with the default
    kernel choice resolved as on a TPU backend: (use_pallas, lowered,
    compiled text)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)
        use_pallas = policies_jax._resolve_use_pallas(None)
        n, t = 65_536, 8_192
        lowered = _grid_lowered(chip, use_pallas, n, t)
    return use_pallas, lowered, lowered.compile().as_text()


def _grid_lowered(chip, use_pallas, n, t):
    args = (_spec(chip, (6, 6), jnp.float32),
            _spec(chip, (t,), jnp.int32), _spec(chip, (t,), jnp.int32),
            _spec(chip, (4, n), jnp.float32), _spec(chip, (4, n), jnp.float32),
            _spec(chip, (n,), jnp.float32), _spec(chip, (n,), jnp.int32),
            _spec(chip, (4,), jnp.int32))
    return policies_jax._sweep_grid.lower(*args, n, use_pallas)


def test_replay_grid_compiles_with_kernel(grid):
    use_pallas, lowered, text = grid
    assert use_pallas
    assert np.prod(lowered.out_info[0].shape) == 96
    assert "tpu_custom_call" in text


def test_replay_grid_kernel_and_relayouts_map_to_victim_scope(grid):
    """The Mosaic call is device time of the step's victim selection, in
    the eviction loop's scope, and it reads the scan's state as it is: no
    reshape, convert, copy or pad produces any of its operands, and none
    makes a whole table of the 96 cells anywhere in the loops."""
    import re

    from repro.launch.hlo_analysis import (_INSTRUCTION, _calls, _operands,
                                           _split_computations, scope_map)
    _, lowered, text = grid
    scopes = scope_map(text, policies_jax.STEP_SCOPES)
    comps = _split_computations(text)
    lines, loop = {}, set()
    for comp, body in comps.items():
        for ls in body:
            m = _INSTRUCTION.match(ls)
            if m:
                lines[m.group(1)] = ls[m.end():]
        for callee, kind in _calls(body):
            if kind == "body":
                loop |= {_INSTRUCTION.match(ls).group(1) for ls in
                         comps[callee] if _INSTRUCTION.match(ls)}

    def opcode(name):
        m = re.match(r"\S+ ([\w\-]+)\(", lines[name])
        return m.group(1) if m else ""

    def producer(name):
        while opcode(name) == "bitcast":
            name = _operands(lines[name])[0]
        return name

    relayouts = ("reshape", "convert", "copy", "pad")
    kernels = [k for k, rest in lines.items()
               if 'custom_call_target="tpu_custom_call"' in rest]
    assert kernels and set(kernels) <= set(scopes["replay.evict_bytes"])
    for k in kernels:
        for o in _operands(lines[k]):
            assert opcode(producer(o)) not in relayouts, (o, lines[o][:200])
    # cells x objects: one per-object table of the whole grid
    table = (np.prod(lowered.out_info[0].shape)
             * lowered.args_info[0][5].shape[0])
    for name in loop:
        dims = re.match(r"\w+\[([\d,]*)\]", lines[name])
        if dims and opcode(name) in relayouts:
            size = np.prod([int(d) for d in dims.group(1).split(",") if d])
            assert size < table, (name, lines[name][:200])
    for scope in policies_jax.STEP_SCOPES:
        assert scopes[scope], scope


@pytest.fixture(scope="module")
def byte_grid(chip):
    """The byte-budget grid of the CDN cell (96 cells, 2^15 objects, 20,000
    steps) with the kernel: (lowered, compiled)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)
        lowered = _grid_lowered(chip, True, 32_768, 20_000)
    return lowered, lowered.compile()


def _loops(text):
    """Instruction lines by name, and the names in each while body and
    condition reached from the entry (nested ones included)."""
    import re

    from repro.launch.hlo_analysis import (_INSTRUCTION, _calls,
                                           _entry_name, _split_computations)
    comps = _split_computations(text)
    lines = {}
    for body in comps.values():
        for ls in body:
            m = _INSTRUCTION.match(ls)
            if m:
                lines[m.group(1)] = ls[m.end():]
    loops, todo = {}, [_entry_name(text)]
    while todo:
        for callee, kind in _calls(comps[todo.pop()]):
            if kind in ("body", "condition") and callee not in loops:
                loops[callee] = [m.group(1) for m in map(_INSTRUCTION.match,
                                                         comps[callee]) if m]
                todo.append(callee)
    whiles = [rest for rest in lines.values()
              if re.search(r"\) while\(", rest)]
    return lines, loops, whiles


def test_byte_grid_kernel_maps_to_evict_bytes_scope(byte_grid, monkeypatch):
    """Under a byte budget the Mosaic call runs inside the eviction loop,
    nested in the scan, and `step_scopes()` puts it in
    `replay.evict_bytes`."""
    lowered, compiled = byte_grid
    text = compiled.as_text()
    monkeypatch.setattr(policies_jax, "_EXECUTABLES", {"grid": compiled})
    scopes = policies_jax.step_scopes()
    lines, loops, whiles = _loops(text)
    kernels = [k for k, rest in lines.items()
               if 'custom_call_target="tpu_custom_call"' in rest]
    assert kernels and set(kernels) <= set(scopes["replay.evict_bytes"])
    assert len(whiles) == 2 and len(loops) == 4
    inner = [names for names in loops.values() if set(kernels) & set(names)]
    assert len(inner) == 1
    for scope in ("replay.score", "replay.evict_bytes", "replay.update"):
        assert scopes[scope]


def test_byte_grid_loop_state_stays_on_chip(byte_grid):
    """Every per-object table that the scan or the eviction loop carries
    is in the core's on-chip memory (`S(1)`), and no relayout, copy or
    select makes a whole table of the 96 cells inside either loop: the
    tables take single-element writes on each trip."""
    import re

    lowered, compiled = byte_grid
    lines, loops, whiles = _loops(compiled.as_text())
    for rest in whiles:
        carries = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}",
                             rest.split(" while(")[0])
        tables = [c for c in carries if re.match(r"\w+\[[\d,]*,128\]", c)]
        assert tables
        for c in tables:
            assert "S(1)" in c, c
    table = np.prod(lowered.out_info[0].shape) * 32_768
    whole = ("reshape", "convert", "copy", "pad", "select", "transpose")
    for names in loops.values():
        for name in names:
            m = re.match(r"\w+\[([\d,]*)\]\S* ([\w\-]+)\(", lines[name])
            if m and m.group(2) in whole:
                size = np.prod([int(d) for d in m.group(1).split(",") if d])
                assert size < table, (name, lines[name][:200])


@pytest.mark.parametrize("program", ["grid", "byte_grid"])
def test_update_writes_aligned_tiles_not_scatters(program, request):
    """The state update writes the inserted object's flag and frozen score
    into the 96 cells' `cached` and `static` tables as one (8, 128) tile
    each: an in-place dynamic-update-slice whose every index the compiler
    knows is aligned, not a scatter of one index a cell. The one scatter
    that writes a whole table of the grid is the eviction loop's, in
    `replay.evict_bytes`."""
    import re

    from repro.launch.hlo_analysis import (_INSTRUCTION, _operands,
                                           _split_computations, scope_map)
    fixture = request.getfixturevalue(program)
    if program == "grid":
        _, lowered, text = fixture
    else:
        lowered, compiled = fixture
        text = compiled.as_text()
    comps = _split_computations(text)
    lines, _, _ = _loops(text)
    scopes = scope_map(text, policies_jax.STEP_SCOPES)
    cells = ",".join(map(str, lowered.out_info[0].shape))
    # a table of the grid as (rows, 128) or as its (rows // 8, 8, 128) view
    whole = re.compile(rf"\w+\[{cells},(\d+,)?\d+,128\]")
    tile = re.compile(rf"\w+\[{cells},(1,)?8,128\]")
    op = re.compile(r"(\w+\[[\d,]*\])\S* ([\w\-]+)\(")

    def runs(name):
        """(name, result type, opcode, text) of each op that the device op
        `name` runs: itself, or each op of the fusion it calls."""
        rest = lines[name]
        callee = re.search(r"\bcalls=%?([\w.\-]+)", rest)
        if re.search(r"\bfusion\(", rest) and callee:
            names = [m.group(1) for m in map(_INSTRUCTION.match,
                                             comps[callee.group(1)]) if m]
        else:
            names = [name]
        for n in names:
            m = op.match(lines[n])
            if m:
                yield n, m.group(1), m.group(2), lines[n]

    writes = {}
    for scope, names in scopes.items():
        for name in names:
            for n, shape, opcode, rest in runs(name):
                if whole.fullmatch(shape):
                    writes.setdefault((scope, opcode), []).append((n, rest))
    scattered = {scope for scope, opcode in writes if opcode == "scatter"}
    assert scattered <= {"replay.evict_bytes"}, scattered
    updates = writes.get(("replay.update", "dynamic-update-slice"), [])
    assert sorted(rest[:3] for _, rest in updates) == ["f32", "s32"], updates
    for n, rest in updates:
        update = _operands(rest)[1]
        assert tile.fullmatch(op.match(lines[update]).group(1)), (n, update)
        aligned = re.search(r'"is_index_aligned":\[([a-z,]*)\]', rest)
        assert aligned and set(aligned.group(1).split(",")) == {"true"}, \
            (n, rest[:300])
