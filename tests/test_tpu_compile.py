"""The emulator's TPU path, compiled for a described TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no chip. Nothing runs: they
show that the chip's compiler accepts the programs the default TPU path
builds at the paper's Table II geometry (and that it refuses the Pallas
kernels that path leaves out). The topology is described inside a
module-scoped fixture, never at import: only one process at a time may
load the TPU library, and every test worker imports this file.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
kernel selection is steered by monkeypatching in the test.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import Engine
from repro.core import Trace, paper_platform
from repro.core.emulator import _emulate_impl, entry_point
from repro.kernels import chunk_step, ops
from repro.sweep import build_points

N_REQ = 4096
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                sharding=sharding)


def _trace_spec(n, sharding):
    i32 = jnp.int32
    return Trace(*(jax.ShapeDtypeStruct((n,), d, sharding=sharding)
                   for d in (i32, i32, jnp.bool_, i32)))


def _valid_spec(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sharding)


def _check_default_path(compiled):
    """No Pallas kernel in the program, and it fits one chip's HBM."""
    assert "tpu_custom_call" not in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used} bytes on a 16 GiB chip"


@pytest.fixture(scope="module")
def run_program(one_chip):
    """``Engine.run``'s compiled entry point at 294,912 pages, chunk 512,
    by ``carried``: the fresh-state program or the donated
    continuation. Each is compiled once for the module."""
    engine = Engine(paper_platform().with_(chunk=512))
    spec = lambda tree: jax.tree.map(lambda x: _spec(x, one_chip), tree)
    compiled = {}

    def get(carried):
        if carried not in compiled:
            state = (spec(jax.eval_shape(engine.init_state)) if carried
                     else None)
            fn = engine._entry_for(N_REQ, carried=carried, donate=carried)
            compiled[carried] = fn.lower(
                engine._static, engine.registry,
                _trace_spec(N_REQ, one_chip), _valid_spec(N_REQ, one_chip),
                state, spec(engine.params), None).compile()
        return compiled[carried]

    return get


@pytest.fixture(scope="module")
def sweep_program(one_chip):
    """The vmapped sweep entry point over the 16-point ``bench_sweep``
    grid rebased onto the paper geometry, compiled once."""
    from benchmarks.bench_sweep import make_spec

    base = paper_platform().with_(chunk=512, hot_threshold=4,
                                  decay_every=32, write_weight=4)
    engine = Engine(base)
    points, registry, params = engine._sweep_batch(
        build_points(make_spec(base)))
    assert len(points) == 16
    fn = entry_point(engine._static, registry, batch=True,
                     shape_sig=(N_REQ, 16, True, None, None))
    return fn.lower(
        engine._static, registry, _trace_spec(N_REQ, one_chip),
        _valid_spec(N_REQ, one_chip), None,
        jax.tree.map(lambda x: _spec(x, one_chip), params), None).compile()


@pytest.mark.parametrize("carried", [False, True], ids=["fresh", "donated"])
def test_engine_run_compiles_at_paper_geometry(run_program, carried):
    """``Engine.run``'s entry point at 294,912 pages, chunk 512: the
    fresh-state program and the donated continuation."""
    compiled = run_program(carried)
    _check_default_path(compiled)
    if carried:
        assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_sweep_compiles_16_points_at_paper_geometry(sweep_program):
    """The vmapped sweep entry point: one program for all points."""
    _check_default_path(sweep_program)


_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\(([^)]*)\)")


def _padded_table_relayouts(hlo: str, n_pages: int) -> list[str]:
    """Names of the ``copy`` and ``reshape`` operations that move the
    whole table into or out of a row-major layout, in which the chip
    pads each 8-lane row to 128 lanes: ``s32[n_pages,8]{1,0…}`` or, for
    a stack of design points, ``s32[P,n_pages,8]{2,1,0…}``."""
    padded = re.compile(rf"^s32\[(?:{n_pages},8\]\{{1,0|\d+,{n_pages},8\]"
                        rf"\{{2,1,0)[:}}]")
    shapes, moves = {}, []
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            name, shape, opcode, args = m.groups()
            shapes[name] = shape
            if opcode in ("copy", "reshape"):
                moves.append((name, shape, args.split(",")[0].strip()[1:]))
    return sorted(name for name, shape, arg in moves
                  if padded.match(shape) or padded.match(shapes.get(arg, "")))


@pytest.mark.parametrize("program,allowed", [("stream", 0), ("sweep", 1)])
def test_commit_leaves_the_table_unpadded(run_program, sweep_program,
                                          program, allowed):
    """The boundary commit is a 2-D (row, lane) scatter-add, so the
    carried table is never relaid out to its padded row-major form
    around it (four passes of the padded table per chunk when the
    commit scattered through a flat view). The donated run has no such
    operation. The sweep keeps one: the retirement stamp's FLAGS
    scatter copies the stacked table (PERF.md, bottleneck (2))."""
    compiled = (run_program(True) if program == "stream"
                else sweep_program)
    found = _padded_table_relayouts(compiled.as_text(),
                                    paper_platform().n_pages)
    assert len(found) <= allowed, found


def test_tpu_selection_rule(monkeypatch):
    """On a TPU backend the default path selects neither emulator kernel
    (both are in ``ops.REFUSED_ON_TPU``); an explicit request still
    does, so it raises there instead of running another path."""
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("REPRO_FORCE_REF", raising=False)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    paper = paper_platform().with_(chunk=512)

    assert ops.REFUSED_ON_TPU == {"hmmu_lookup", "chunk_step"}
    assert not ops.use_pallas("hmmu_lookup")
    assert not ops.use_pallas("chunk_step")
    assert ops.use_pallas("flash_attention")
    assert not ops._interpret()
    assert not chunk_step.use_chunk_step_kernel(paper)
    assert chunk_step.use_chunk_step_kernel(paper.with_(chunk_step_kernel="on"))

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert ops.use_pallas("hmmu_lookup")
    assert chunk_step.use_chunk_step_kernel(paper)
    assert not chunk_step.use_chunk_step_kernel(
        paper.with_(chunk_step_kernel="off"))


def _raises_when_compiled(lower):
    with pytest.raises(Exception) as info:
        lower().compile()
    return str(info.value)


def test_tpu_refuses_hmmu_lookup_kernel(one_chip, monkeypatch):
    """Why ``hmmu_lookup`` is off the TPU path: its (1, 1, 8) row block
    is below the (8, 128) int32 tile. REPRO_FORCE_PALLAS=1 reaches the
    compiler and raises."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n_pages = paper_platform().n_pages
    table = jax.ShapeDtypeStruct((n_pages, 8), jnp.int32, sharding=one_chip)
    pages = jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip)
    msg = _raises_when_compiled(
        lambda: jax.jit(lambda t, p: ops.hmmu_lookup(t, p)).lower(table,
                                                                 pages))
    assert "divisible by 8 and 128" in msg


def test_tpu_refuses_chunk_step_kernel(one_chip, monkeypatch):
    """Why the one-kernel chunk step is off the TPU path: the body reads
    a vector from the scalar-prefetch (SMEM) operand.
    ``chunk_step_kernel="on"`` reaches the compiler and raises."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = paper_platform().with_(chunk=512, chunk_step_kernel="on")
    engine = Engine(cfg)
    params = jax.tree.map(lambda x: _spec(x, one_chip), engine.params)
    fn = jax.jit(lambda t, v, p: _emulate_impl(engine._static,
                                               engine.registry, t, v, None,
                                               p))
    msg = _raises_when_compiled(
        lambda: fn.lower(_trace_spec(N_REQ, one_chip),
                         _valid_spec(N_REQ, one_chip), params))
    assert "Can only load scalars from SMEM" in msg
