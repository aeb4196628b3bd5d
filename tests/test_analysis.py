"""The reprolint suite checks itself: every pass must (a) run clean on
this repo and (b) demonstrably FAIL — non-zero exit with a file:line
finding — on its seeded-violation fixture in tests/analysis_fixtures/.

The CLI contract is tested through real subprocesses (exit codes are the
CI interface); the checker internals get direct unit tests, including
deliberately-broken inputs the fixtures can't express (a doctored
static-key allowlist, an aliasing-free lowering, pragma suppression).
"""
from __future__ import annotations

import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "tests" / "analysis_fixtures"
PASSES = ("schedule", "donation", "lanes", "staticness", "tripwire",
          "docrefs", "ranges", "pallas_san")


def _cli(*args):
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{env.get('PYTHONPATH', '')}"
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=ROOT, env=env)


@pytest.mark.parametrize("name", PASSES)
def test_pass_fails_on_seeded_fixture(name):
    fixture = FIXTURES / f"bad_{name}.py"
    r = _cli("--pass", name, str(fixture))
    assert r.returncode != 0, \
        f"{name} pass must fail on its fixture\n{r.stdout}\n{r.stderr}"
    assert re.search(rf"bad_{name}\.py:\d+: \[{name}\]", r.stdout), \
        f"no file:line finding in output:\n{r.stdout}"


def test_cli_clean_on_repo():
    """The whole suite exits 0 on the merged tree (the CI gate)."""
    r = _cli("--check")
    assert r.returncode == 0, \
        f"reprolint must run clean on the repo:\n{r.stdout}\n{r.stderr}"
    assert "0 finding(s)" in r.stdout


def test_cli_report_json(tmp_path):
    report = tmp_path / "findings.json"
    r = _cli("--pass", "lanes", "--report", str(report),
             str(FIXTURES / "bad_lanes.py"))
    assert r.returncode != 0
    import json

    data = json.loads(report.read_text())
    assert set(data) == {"findings", "proved_bounds", "stats"}
    rows = data["findings"]
    assert rows and all(
        set(row) == {"path", "line", "pass_name", "message"}
        for row in rows)
    assert data["stats"]["total"] >= data["stats"]["lanes"] >= 0


def test_cli_report_proved_bounds(tmp_path):
    """A repo-mode ranges run ships per-program budget proofs (the
    per-chunk growth G, the horizon, and the proved per-lane bounds)."""
    report = tmp_path / "bounds.json"
    r = _cli("--pass", "ranges", "--report", str(report))
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    import json

    bounds = json.loads(report.read_text())["proved_bounds"]
    labels = {b["label"] for b in bounds}
    assert labels == {"scan-path", "pallas-body", "jnp-ref"}
    for b in bounds:
        assert b["int32_horizon_chunks"] >= b["n_chunks_budget"]
        assert b["table_gathers_proved"] > 0
    lanes = next(b for b in bounds if b["label"] == "jnp-ref")["lanes"]
    assert lanes["HOTNESS"][1] <= 2**29 and lanes["WEAR"][1] <= 2**29


def test_cli_baseline_diff(tmp_path):
    """--baseline makes known findings informational: same fixture twice
    exits 0; adding a second violating fixture exits 1 again."""
    base = tmp_path / "base.json"
    bad = str(FIXTURES / "bad_ranges.py")
    r = _cli("--pass", "ranges", "--report", str(base), bad)
    assert r.returncode != 0
    r = _cli("--pass", "ranges", "--baseline", str(base), bad)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "0 new vs baseline" in r.stdout
    r = _cli("--pass", "ranges", "--pass", "pallas_san",
             "--baseline", str(base), bad,
             str(FIXTURES / "bad_pallas_san.py"))
    assert r.returncode != 0
    assert "new vs baseline" in r.stdout


# --- checker internals ----------------------------------------------------


def test_schedule_checker_clean_on_good_step():
    import jax
    import jax.numpy as jnp

    from repro.analysis import schedule

    def good_step(table, pages, w):
        hot = table[pages, 2]  # gather before the commit
        rows = jnp.concatenate([pages, pages])
        lanes = jnp.concatenate([jax.lax.full((4,), 2, i32),
                                 jax.lax.full((4,), 5, i32)])
        t2 = table.at[rows, lanes].add(jnp.concatenate([w + hot, w]),
                                       mode="drop")
        return t2[pages, 3]  # committed-table read

    i32 = jnp.int32
    jaxpr = jax.make_jaxpr(good_step)(
        jnp.zeros((16, 8), i32), jnp.arange(4, dtype=i32),
        jnp.ones(4, i32))
    assert schedule.check_jaxpr_schedule(jaxpr, 0, label="good") == []


def test_schedule_checker_flags_flattened_commit():
    """A commit through a ``reshape(-1)`` view of the packed table made
    inside the step is a finding: on a TPU the reshape relays out the
    padded table (the scan carries the flat form instead)."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import schedule

    def flat_step(table, pages, w):
        hot = table[pages, 2]
        flat = table.reshape(-1)
        t2 = flat.at[pages * 8 + 2].add(w + hot, mode="drop")
        return t2.reshape(table.shape)[pages, 3]

    i32 = jnp.int32
    jaxpr = jax.make_jaxpr(flat_step)(
        jnp.zeros((16, 8), i32), jnp.arange(4, dtype=i32),
        jnp.ones(4, i32))
    msgs = [f.message
            for f in schedule.check_jaxpr_schedule(jaxpr, 0, label="flat")]
    assert sum("reshape of the table" in m and "128 lanes" in m
               for m in msgs) == 2, msgs
    assert any("not a 2-D (row, lane) scatter-add" in m for m in msgs), msgs


def test_schedule_checker_clean_on_flat_carried_step():
    """The flat lane-major form the scan carries: gathers, ONE 1-D
    scatter-add, a one-word post-commit stamp and a lane read of the
    committed table keep the schedule."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import schedule

    n = 16

    def flat_step(table, pages, w):
        hot = table[2 * n + pages]  # gather before the commit
        idx = jnp.concatenate([2 * n + pages, 5 * n + pages])
        t2 = table.at[idx].add(jnp.concatenate([w + hot, w]), mode="drop")
        t2 = t2.at[6 * n + pages[0]].set(1, mode="drop")  # the stamp
        return jnp.min(t2[3 * n:4 * n])  # committed-table lane read

    i32 = jnp.int32
    jaxpr = jax.make_jaxpr(flat_step)(
        jnp.zeros((8 * n,), i32), jnp.arange(4, dtype=i32),
        jnp.ones(4, i32))
    assert schedule.check_jaxpr_schedule(jaxpr, 0, label="flat") == []


def test_schedule_pass_checks_the_flat_scan_carry():
    """The pass traces the scan path with either carried form and finds
    the commit in its body; converting back to packed inside the step
    would be a finding."""
    import jax.numpy as jnp

    from repro.analysis import schedule
    from repro.analysis.common import scan_body_info
    from repro.core.config import small_platform
    from repro.core.emulator import as_registry

    cfg = small_platform()
    for flat, shape in ((True, (8 * cfg.n_pages,)),
                        (False, (cfg.n_pages, 8))):
        info, err = scan_body_info(cfg, as_registry(None), flat=flat)
        assert err is None, err
        carry = info["body"].invars[info["table_index"]]
        assert tuple(carry.aval.shape) == shape
        assert schedule.check_jaxpr_schedule(
            info["body"], info["table_index"], label="scan-path") == []

    import jax

    def repacking_step(table, pages, w):
        packed = table.reshape(8, -1).T
        return packed.at[pages, 2].add(w, mode="drop")

    jaxpr = jax.make_jaxpr(repacking_step)(
        jnp.zeros((8 * cfg.n_pages,), jnp.int32),
        jnp.arange(4, dtype=jnp.int32), jnp.ones(4, jnp.int32))
    msgs = [f.message for f in
            schedule.check_jaxpr_schedule(jaxpr, 0, label="repack")]
    assert any("reshape of the table inside the step" in m
               for m in msgs), msgs


def test_schedule_checker_flags_missing_commit():
    import jax
    import jax.numpy as jnp

    from repro.analysis import schedule

    jaxpr = jax.make_jaxpr(lambda t: t[0, 2])(
        jnp.zeros((16, 8), jnp.int32))
    findings = schedule.check_jaxpr_schedule(jaxpr, 0, label="nocommit")
    assert any("no (row, lane) scatter-add" in f.message for f in findings)


@pytest.mark.parametrize("form", ["row_lane", "flat"])
def test_ranges_attributes_commit_sections_to_their_lanes(form):
    """The ranges prover charges each section of the commit scatter to
    the lane it targets, whether the commit names (row, lane) with a
    constant lane column or indexes a flat view: an unsaturated add on
    HOTNESS and WEAR leaves the other lanes proved."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import ranges

    i32 = jnp.int32

    def step(table, pages, w):
        upd = jnp.concatenate([jnp.minimum(w, 3)] * 2)
        if form == "flat":
            idx = jnp.concatenate([pages * 8 + 2, pages * 8 + 3])
            return table.reshape(-1).at[idx].add(upd, mode="drop") \
                .reshape(table.shape)
        lanes = jnp.concatenate([jax.lax.full((4,), 2, i32),
                                 jax.lax.full((4,), 3, i32)])
        return table.at[jnp.concatenate([pages, pages]), lanes].add(
            upd, mode="drop")

    jaxpr = jax.make_jaxpr(step)(jnp.zeros((16, 8), i32),
                                 jnp.arange(4, dtype=i32), jnp.ones(4, i32))
    flagged = {f.message.split()[1]
               for f in ranges.check_fixture(jaxpr, form)}
    assert flagged == {"HOTNESS", "WEAR"}


def test_donation_aliasing_parser_sees_alias():
    import jax
    import jax.numpy as jnp

    from repro.analysis import donation

    fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    txt = fn.lower(jnp.zeros((8, 8), jnp.int32)).as_text()
    dims, aliased = donation._aliased_args(txt)
    assert dims[0] == "8x8"
    assert 0 in aliased


def test_donation_read_after_donate_rebind_is_clean():
    from repro.analysis import donation

    src = (
        "def ok(engine, trace, state):\n"
        "    state, outs = engine.run(trace, state=state)\n"
        "    return state.table, outs\n"
        "\n"
        "def explicit_no_donate(engine, trace, state):\n"
        "    out = engine.run(trace, state=state, donate=False)\n"
        "    return state.table, out\n")
    import ast

    assert donation._check_read_after_donate(ast.parse(src), "x.py") == []


def test_donation_read_after_donate_flags_leak():
    import ast

    from repro.analysis import donation

    src = (
        "def leak(engine, trace, state):\n"
        "    out = engine.run(trace, state=state)\n"
        "    return out, state.table\n")
    findings = donation._check_read_after_donate(ast.parse(src), "x.py")
    assert len(findings) == 1 and findings[0].line == 3


def test_lanes_pragma_suppresses():
    from repro.analysis import lanes

    src = (
        "from repro.core import table as table_lib\n"
        "\n"
        "def peek(table, pages):\n"
        "    # reprolint: allow[lanes] layout probe for a debug dump\n"
        "    return table[pages, table_lib.HOTNESS]\n")
    assert lanes.check_source(src, "x.py") == []
    # same source without the pragma: flagged
    assert lanes.check_source(src.replace(
        "    # reprolint: allow[lanes] layout probe for a debug dump\n",
        ""), "x.py") != []


def test_staticness_completeness_detects_uncovered_knob(monkeypatch):
    """Un-allowlist the known-inert TechnologyParams subfields: the
    perturbation checker must report them as reaching neither
    static_key nor RuntimeParams."""
    from repro.analysis import common, staticness

    monkeypatch.setattr(staticness, "INERT_SUBFIELDS", set())
    findings = staticness.check_static_key_completeness(common.repo_root())
    assert any("endurance_log10" in f.message and "NEITHER" in f.message
               for f in findings)


def test_staticness_repo_fields_all_perturbable():
    from repro.analysis import common, staticness

    findings = staticness.check_static_key_completeness(common.repo_root())
    assert findings == [], [f.format() for f in findings]


def test_tripwire_passes_when_flat_and_raises_on_compile():
    import jax.numpy as jnp

    from repro import Engine
    from repro.analysis import RecompileError, assert_compile_flat
    from repro.core import small_platform
    from repro.core.emulator import Trace

    # distinct geometry: never collides with other tests' compile counts
    eng = Engine(small_platform(n_fast_pages=4, n_slow_pages=20, chunk=4))
    i32 = jnp.int32
    trace = Trace(page=jnp.zeros(4, i32), offset=jnp.zeros(4, i32),
                  is_write=jnp.zeros(4, bool), size=jnp.full(4, 64, i32))
    with assert_compile_flat(eng, allow=1) as cc:
        eng.run(trace)  # cold: exactly one new entry
    assert cc.count == 1
    with assert_compile_flat(eng):
        eng.run(trace)  # warm: flat
    with pytest.raises(RecompileError, match="new emulation entry"):
        with assert_compile_flat(eng):
            eng.run(Trace(*(jnp.resize(x, 8) for x in trace)))


def test_docrefs_tokens():
    from repro.analysis import docrefs

    findings = docrefs.check_source(
        "# port of the old run_sweep helper\n", "x.py")
    assert findings and findings[0].line == 1
    assert docrefs.check_source(
        "state = engine.run_stream(segments)\n", "x.py") == []
