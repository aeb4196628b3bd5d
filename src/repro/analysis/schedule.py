"""Pass ``schedule`` — the one-true chunk read/write schedule, checked at
the jaxpr level.

The contract (kernels/chunk_step.py, PR 7) that eliminated the 12.3x
table-copy regression class:

  1. every table *read* (the stage-2 lookup gather, the swap-pair rows,
     the policy's candidate scans) happens against the pre-chunk table or
     the committed table — never against a partially-written copy;
  2. the chunk's writes collapse into ONE 2-D (row, lane) int32
     scatter-add (the boundary commit) on the pre-chunk table;
  3. after the commit the only further table writes are the (documented)
     decay cond and the retirement's single-row FLAGS stamp;
  4. no intermediate whole-table copies exist at all, and the table is
     never reshaped: a TPU pads each 8-lane row to 128 lanes, so a flat
     view forces a relayout of the whole padded table.

This pass traces the step with ``jax.make_jaxpr`` and walks the
equations, tracking the lineage of the table value (writes bump a
generation counter; a reshape is flagged and then followed as a view).
It checks THREE programs:

  * the scan-path chunk body — the sub-jaxpr of the ``lax.scan`` inside
    ``emulator._emulate_impl`` (what a normal run actually compiles);
  * ``step_ref(..., seq=True)`` — the literal Pallas kernel body
    (``_pallas_step_fn._body`` calls it; an AST check below pins that
    link so tracing ``seq=True`` IS checking the kernel);
  * ``step_ref(..., seq=False)`` — the jnp reference.

Fixture protocol: a ``reprolint_case()`` returning
``{"kind": "schedule", "make": lambda: (fn, args)}``; ``fn(*args)`` is
traced with the table as argument 0.
"""
from __future__ import annotations

import ast
import pathlib

from .common import Finding, eqn_loc, rel, scan_body_info, trace_step_ref

from jax.extend.core import Literal, Var

PASS = "schedule"

# Primitives that only *read* their table operand and that we expect to
# see in the step trace. Anything else that consumes the table and emits
# a table-shaped value is flagged as an unrecognized table write/copy.
_WRITE_PRIMS = ("scatter", "scatter-apply", "dynamic_update_slice")


# Shared with the ranges pass (analysis/common.py).
_loc = eqn_loc


def check_jaxpr_schedule(jaxpr, table_invar_index: int = 0,
                         label: str = "step") -> list[Finding]:
    """Walk one jaxpr and enforce the chunk schedule on the table whose
    lineage starts at ``invars[table_invar_index]``."""
    core = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    tvar = core.invars[table_invar_index]
    tshape = tuple(tvar.aval.shape)
    findings: list[Finding] = []

    def bad(eqn, msg):
        path, line = _loc(eqn)
        findings.append(Finding(path, line, PASS, f"[{label}] {msg}"))

    gen: dict[Var, int] = {tvar: 0}
    commit_seen = False
    pre_gathers = 0
    post_row_scatters = 0
    post_conds = 0
    for eqn in core.eqns:
        ins = [v for v in eqn.invars
               if isinstance(v, Var) and not isinstance(v, Literal)
               and v in gen]
        if not ins:
            continue
        g = max(gen[v] for v in ins)
        prim = eqn.primitive.name
        t_outs = [o for o in eqn.outvars
                  if tuple(getattr(o.aval, "shape", ())) == tshape]
        if prim == "reshape":
            bad(eqn, "reshape of the table — on a TPU the 8-lane rows are "
                     "padded to 128 lanes, so a flat view relays out the "
                     "whole padded table; scatter on (row, lane) instead")
            gen[eqn.outvars[0]] = g  # followed as a view of the table
            continue
        if prim == "scatter-add":
            if g == 0:
                if commit_seen:
                    bad(eqn, "second scatter-add on the pre-chunk table — "
                             "the boundary commit must be the ONE combined "
                             "scatter")
                else:
                    commit_seen = True
                    dims = eqn.params["dimension_numbers"]
                    if (tuple(eqn.invars[0].aval.shape) != tshape
                            or tuple(dims.scatter_dims_to_operand_dims)
                            != tuple(range(len(tshape)))):
                        bad(eqn, "boundary commit is not a 2-D (row, lane) "
                                 "scatter-add on the table")
            else:
                bad(eqn, "extra scatter-add on the committed table")
            for o in eqn.outvars:
                gen[o] = g + 1
            continue
        if prim in _WRITE_PRIMS:
            if g == 0:
                bad(eqn, f"table write (`{prim}`) before the boundary "
                         "commit — all pre-commit table access must be "
                         "reads")
            else:
                upd = eqn.invars[-1]
                n_upd = 1
                for d in getattr(upd.aval, "shape", ()):
                    n_upd *= d
                if n_upd > tshape[-1]:
                    bad(eqn, f"post-commit `{prim}` larger than one table "
                             "row — only the retirement's single-row FLAGS "
                             "stamp may follow the commit")
                post_row_scatters += 1
                if post_row_scatters > 1:
                    bad(eqn, "more than one post-commit row scatter (the "
                             "retirement stamp must be the only one)")
            for o in t_outs:
                gen[o] = g + 1
            continue
        if prim == "cond":
            if t_outs:
                if g == 0:
                    bad(eqn, "table-writing cond before the boundary commit")
                post_conds += 1
                if post_conds > 1:
                    bad(eqn, "more than one table-writing cond (only the "
                             "decay branch may rewrite the table)")
                for o in t_outs:
                    gen[o] = g + 1
            elif g == 0 and commit_seen:
                bad(eqn, "cond reads the pre-commit table after the "
                         "boundary commit (stale read)")
            continue
        if prim == "copy" or (prim == "convert_element_type" and t_outs):
            bad(eqn, f"intermediate table copy (`{prim}`) — the schedule "
                     "allows zero whole-table copies")
            for o in t_outs:
                gen[o] = g
            continue
        if t_outs:
            bad(eqn, f"unrecognized table-producing op `{prim}` — the "
                     "boundary commit must be the only table write")
            for o in t_outs:
                gen[o] = g
            continue
        # pure read
        if g == 0:
            if commit_seen:
                bad(eqn, f"read of the pre-commit table (`{prim}`) after "
                         "the boundary commit (stale schedule)")
            else:
                pre_gathers += 1
    if not commit_seen:
        findings.append(Finding(
            f"<{label}>", 0, PASS,
            f"[{label}] no (row, lane) scatter-add boundary commit found"))
    elif pre_gathers == 0:
        findings.append(Finding(
            f"<{label}>", 0, PASS,
            f"[{label}] no table gather precedes the boundary commit"))
    return findings


def _trace_step_ref(cfg, registry, seq: bool):
    """One-chunk ``step_ref`` trace (path-linking machinery now lives in
    analysis/common.py — the ranges pass shares it)."""
    jaxpr, _names, _out_names = trace_step_ref(cfg, registry, seq)
    return jaxpr


def _scan_body_jaxpr(cfg, registry):
    """The chunk body of the compiled scan path (via
    :func:`common.scan_body_info`) as ``((body, table_index), err)``."""
    info, err = scan_body_info(cfg, registry)
    if err is not None:
        return None, err
    return (info["body"], info["table_index"]), None


def _check_pallas_body_link(root: pathlib.Path) -> list[Finding]:
    """AST-pin the fact that the Pallas kernel body IS
    ``step_ref(seq=True)``: ``_body`` inside ``_pallas_step_fn`` must
    call ``step_ref`` with ``seq=True``. If that link ever breaks, the
    seq=True trace below no longer covers the kernel and this pass must
    be retargeted."""
    path = root / "src" / "repro" / "kernels" / "chunk_step.py"
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == "_body":
            for call in ast.walk(node):
                if (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Name)
                        and call.func.id == "step_ref"
                        and any(k.arg == "seq"
                                and isinstance(k.value, ast.Constant)
                                and k.value.value is True
                                for k in call.keywords)):
                    return []
            return [Finding(rel(path), node.lineno, PASS,
                            "_pallas_step_fn._body no longer calls "
                            "step_ref(seq=True) — the seq=True schedule "
                            "trace no longer covers the Pallas kernel")]
    return [Finding(rel(path), 1, PASS,
                    "could not find _body in kernels/chunk_step.py — "
                    "the Pallas-body link check needs updating")]


def run_repo(root: pathlib.Path) -> list[Finding]:
    from repro.core.config import small_platform
    from repro.core.emulator import as_registry

    cfg = small_platform()
    registry = as_registry(None)
    findings = _check_pallas_body_link(root)
    body, err = _scan_body_jaxpr(cfg, registry)
    if err is not None:
        findings.append(Finding("src/repro/core/emulator.py", 1, PASS, err))
    else:
        findings += check_jaxpr_schedule(body[0], body[1],
                                         label="scan-path")
    findings += check_jaxpr_schedule(
        _trace_step_ref(cfg, registry, seq=True), 0, label="pallas-body")
    findings += check_jaxpr_schedule(
        _trace_step_ref(cfg, registry, seq=False), 0, label="jnp-ref")
    return findings


def run_paths(paths) -> list[Finding]:
    import jax

    from .common import fixture_case

    findings: list[Finding] = []
    for path in paths:
        case = fixture_case(path)
        if not case or case.get("kind") != "schedule":
            continue
        fn, args = case["make"]()
        jaxpr = jax.make_jaxpr(fn)(*args)
        findings += check_jaxpr_schedule(
            jaxpr, case.get("table_invar_index", 0),
            label=pathlib.Path(path).stem)
    return findings
