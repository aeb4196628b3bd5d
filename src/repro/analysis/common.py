"""Shared plumbing for the reprolint passes.

A *finding* is one contract violation at a file:line. Passes return
``list[Finding]``; the CLI renders them ``path:line: [pass] message`` and
exits non-zero when any survive. A finding on a line carrying a

    # reprolint: allow[<pass>] <reason>

pragma is suppressed — the pragma must name the pass (comma-separate to
allow several) and should state *why* the exemption is sound, because the
lint exists precisely where reviewer memory failed before.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import re

# Directories (relative to the repo root) that the AST passes sweep by
# default. Tests are excluded: they deliberately poke at internals (and
# the seeded-violation fixtures under tests/analysis_fixtures MUST keep
# violating). The analysis package itself is excluded from text-level
# scans — it names the banned tokens as data.
DEFAULT_SCAN_DIRS = ("src/repro", "benchmarks", "examples")

_PRAGMA_RE = re.compile(r"#\s*reprolint:\s*allow\[([a-z0-9_,\s-]+)\]")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation at ``path:line``."""

    path: str
    line: int
    pass_name: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.pass_name}] {self.message}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def repo_root() -> pathlib.Path:
    """The repository root (parent of ``src/repro``), resolved from this
    file so the CLI works from any cwd."""
    root = pathlib.Path(__file__).resolve().parents[3]
    if not (root / "src" / "repro").is_dir():  # installed copy: fall back
        root = pathlib.Path.cwd()
    return root


def rel(path: pathlib.Path | str, root: pathlib.Path | None = None) -> str:
    """Repo-relative display path (absolute when outside the repo)."""
    p = pathlib.Path(path).resolve()
    root = root or repo_root()
    try:
        return str(p.relative_to(root))
    except ValueError:
        return str(p)


def iter_py_files(root: pathlib.Path,
                  subdirs=DEFAULT_SCAN_DIRS) -> list[pathlib.Path]:
    out: list[pathlib.Path] = []
    for sub in subdirs:
        base = root / sub
        if not base.exists():
            continue
        out.extend(p for p in sorted(base.rglob("*.py"))
                   if "__pycache__" not in p.parts)
    return out


def pragma_lines(source: str) -> dict[int, set[str]]:
    """Map of 1-based line number -> pass names allowed on that line.

    An inline pragma covers its own line; a pragma on a comment-only
    line covers the next code line (comment/blank lines in between are
    skipped, so a pragma can open a multi-line explanation)."""
    out: dict[int, set[str]] = {}
    pending: set[str] = set()
    for i, text in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(text)
        stripped = text.strip()
        if m:
            passes = {p.strip() for p in m.group(1).split(",") if p.strip()}
            if stripped.startswith("#"):
                pending |= passes
            else:
                out.setdefault(i, set()).update(passes)
        if stripped.startswith("#") or not stripped:
            continue
        if pending:
            out.setdefault(i, set()).update(pending)
            pending = set()
    return out


def apply_pragmas(findings: list[Finding], source: str) -> list[Finding]:
    """Drop findings whose line carries an allow-pragma for their pass."""
    allowed = pragma_lines(source)
    return [f for f in findings
            if f.pass_name not in allowed.get(f.line, ())]


# --------------------------------------------------------------------------- #
# Path-linking machinery: building and tracing the emulator's two compiled
# chunk-step programs (the `lax.scan` body of `_emulate_impl` and the
# Pallas kernel body via ``step_ref(seq=True)``). Grown out of the
# schedule pass (PR 9); the ranges pass reuses it with params/faults as
# *traced inputs* so its interval proofs are parametric over the runtime
# knobs instead of specialized to one config's values.
# --------------------------------------------------------------------------- #


def eqn_loc(eqn, default=("<jaxpr>", 0)):
    """(repo-relative path, line) of a jaxpr equation's user frame."""
    try:
        from jax._src import source_info_util

        fr = source_info_util.user_frame(eqn.source_info)
        if fr is not None:
            return rel(fr.file_name), fr.start_line
    except Exception:
        pass
    return default


def step_args(cfg, *, nt: int = 2, nd: int = 2):
    """(params, faults, call_args) for tracing one chunk step.

    ``call_args`` is the positional tail of ``step_ref`` after
    ``(cfg, registry, table, params, ...)``: ``(table, sc, bank_free,
    page, offset, is_write, size, valid)``. ``faults`` is a shaped (not
    empty) plan so cursor arithmetic stays symbolic — a sentinel-only
    plan constant-folds the death detector away and the trace would no
    longer cover fault consumption."""
    import jax.numpy as jnp

    from repro.core import emulator as emu
    from repro.core import faults as faults_lib
    from repro.core.config import RuntimeParams
    from repro.kernels import chunk_step as cs

    params = RuntimeParams.from_config(cfg)
    state = emu.init_state(cfg, params)
    sc = cs.StepScalars(
        clock=state.clock, clock_ptr=state.clock_ptr,
        chunk_idx=state.chunk_idx, dma=state.dma,
        link_free_rx=state.link_free_rx, link_free_tx=state.link_free_tx,
        last_return=state.last_return, rescue_page=state.rescue_page,
        min_wear=state.min_wear, fault_cursor=state.fault_cursor)
    faults = faults_lib.pad_plan(faults_lib.FaultPlan.empty(), nt, nd)
    n = cfg.chunk
    i32 = jnp.int32
    page = jnp.zeros(n, i32)
    offset = jnp.zeros(n, i32)
    is_write = jnp.zeros(n, bool)
    size = jnp.full(n, cfg.line_size, i32)
    valid = jnp.ones(n, bool)
    return params, faults, (state.table, sc, state.bank_free,
                            page, offset, is_write, size, valid)


def _leaf_names(prefix, tree):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _leaf in flat:
        name = prefix + "".join(
            f".{p.name}" if hasattr(p, "name") else f"[{p.idx}]"
            if hasattr(p, "idx") else str(p) for p in path)
        out.append(name)
    return out


def trace_step_ref(cfg, registry, seq: bool, *,
                   params_as_inputs: bool = False):
    """Trace ``step_ref`` for one chunk. Returns
    ``(jaxpr, names, out_names)``: ``names[i]`` labels
    ``jaxpr.jaxpr.invars[i]`` and ``out_names[i]`` labels
    ``jaxpr.jaxpr.outvars[i]`` (dotted pytree paths, e.g.
    ``"params.write_weight"`` / ``"sc.dma.page_a"``), or None for both
    when ``params_as_inputs`` is False (params/faults closed over as
    constants — the schedule pass's historical shape)."""
    import jax

    from repro.kernels import chunk_step as cs

    params, faults, (table, sc, bank_free, page, offset, is_write, size,
                     valid) = step_args(cfg)

    if not params_as_inputs:
        def fn(table, sc, bank_free, page, offset, is_write, size, valid):
            return cs.step_ref(cfg, registry, table, params, sc, bank_free,
                               page, offset, is_write, size, valid, None,
                               seq=seq)

        return jax.make_jaxpr(fn)(table, sc, bank_free, page, offset,
                                  is_write, size, valid), None, None

    def fn(table, params, sc, bank_free, page, offset, is_write, size,
           valid, faults):
        return cs.step_ref(cfg, registry, table, params, sc, bank_free,
                           page, offset, is_write, size, valid, faults,
                           seq=seq)

    args = (table, params, sc, bank_free, page, offset, is_write, size,
            valid, faults)
    arg_names = ("table", "params", "sc", "bank_free", "page", "offset",
                 "is_write", "size", "valid", "faults")
    names = []
    for prefix, arg in zip(arg_names, args):
        names += _leaf_names(prefix, arg)
    jaxpr = jax.make_jaxpr(fn)(*args)
    assert len(names) == len(jaxpr.jaxpr.invars), \
        (len(names), len(jaxpr.jaxpr.invars))
    # step_ref returns (table, sc', bank_free', outs-dict); label the
    # flattened outvars the same way so the ranges pass can map its
    # monitored fields.
    out_struct = jax.eval_shape(fn, *args)
    out_names = _leaf_names("out", out_struct)
    # the out tree is (table, sc, bank_free, outs) — relabel the first
    # three to the canonical field names.
    fixed = []
    for nm in out_names:
        nm = nm.replace("out[0]", "table").replace("out[1]", "sc") \
               .replace("out[2]", "bank_free").replace("out[3]", "outs")
        fixed.append(nm)
    assert len(fixed) == len(jaxpr.jaxpr.outvars), \
        (len(fixed), len(jaxpr.jaxpr.outvars))
    return jaxpr, names, fixed


def scan_body_info(cfg, registry, *, flat: bool | None = None):
    """The chunk body of the compiled scan path, with enough structure to
    map its invars: trace ``_emulate_impl`` (params concrete, faults a
    shaped traced input) and pull the ``scan`` equation. ``flat`` forces
    the carried table form (``emulator.FLAT_CARRY_MIN_BYTES`` moved to
    either side of the table while tracing); None traces the form
    ``cfg``'s table size selects.

    Returns ``(info, err)`` where info is a dict with:

    * ``outer``: the traced ClosedJaxpr of ``_emulate_impl``;
    * ``outer_names``: dotted labels of the outer invars (trace/faults);
    * ``scan_eqn``: the scan equation inside it;
    * ``body``: the scan body (open) jaxpr;
    * ``num_consts`` / ``num_carry``: the scan's split of body invars;
    * ``carry_names``: dotted ``EmulatorState`` leaf labels for body
      invars ``[num_consts : num_consts + num_carry]`` (flattening order
      of the carry pytree is the flattening order of the state);
    * ``table_index``: body invar index of the table carry, in the form
      the scan carries it (packed ``[n_pages, 8]`` or flat lane-major
      ``[8 * n_pages]``).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import emulator as emu
    from repro.core import faults as faults_lib
    from repro.core.config import RuntimeParams

    n = cfg.chunk  # one chunk is enough — the body is per-chunk
    i32 = jnp.int32
    trace = emu.Trace(page=jnp.zeros(n, i32), offset=jnp.zeros(n, i32),
                      is_write=jnp.zeros(n, bool),
                      size=jnp.full(n, cfg.line_size, i32))
    faults = faults_lib.pad_plan(faults_lib.FaultPlan.empty(), 2, 2)
    params = RuntimeParams.from_config(cfg)
    state = emu.init_state(cfg, params)

    def fn(trace, faults):
        return emu._emulate_impl(cfg, registry, trace, faults=faults)

    threshold = emu.FLAT_CARRY_MIN_BYTES
    if flat is not None:
        emu.FLAT_CARRY_MIN_BYTES = 0 if flat else 2**62
    try:
        outer = jax.make_jaxpr(fn)(trace, faults)
    finally:
        emu.FLAT_CARRY_MIN_BYTES = threshold
    outer_names = _leaf_names("trace", trace) + _leaf_names("faults", faults)
    scans = [e for e in outer.jaxpr.eqns if e.primitive.name == "scan"]
    if not scans:
        return None, "no `scan` equation found in _emulate_impl"
    eqn = scans[0]
    body = eqn.params["jaxpr"].jaxpr
    num_consts = eqn.params["num_consts"]
    num_carry = eqn.params["num_carry"]
    carry_names = _leaf_names("state", state)
    if len(carry_names) != num_carry:
        return None, (f"scan carries {num_carry} leaves but EmulatorState "
                      f"flattens to {len(carry_names)} — the carry mapping "
                      "needs retargeting")
    tshapes = ((cfg.n_pages, 8), (8 * cfg.n_pages,))
    idx = [i for i, v in enumerate(body.invars)
           if tuple(v.aval.shape) in tshapes]
    if len(idx) != 1:
        return None, (f"expected exactly one table carry {tshapes} in the "
                      f"scan body, found {len(idx)}")
    return {"outer": outer, "outer_names": outer_names, "scan_eqn": eqn,
            "body": body, "num_consts": num_consts, "num_carry": num_carry,
            "carry_names": carry_names, "table_index": idx[0]}, None


def load_module_from_path(path: pathlib.Path):
    """Import a fixture module by file path (no package side effects)."""
    path = pathlib.Path(path)
    spec = importlib.util.spec_from_file_location(
        f"_reprolint_fixture_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fixture_case(path: pathlib.Path):
    """The ``reprolint_case()`` dict of a fixture module, or None."""
    mod = load_module_from_path(path)
    case = getattr(mod, "reprolint_case", None)
    return case() if case is not None else None
