"""Tests for the contract-lint suite (repro.analysis; DESIGN.md §13).

Layout:
  * per-rule good/bad fixture pairs under tests/fixtures/lint/ — every
    bad fixture must trigger its rule (exact count), every good twin
    must be completely clean;
  * pragma machinery (suppression, LNT001 malformed, LNT002 unused,
    pragmas inside docstrings ignored);
  * baseline round-trip + the zero-drift property in both directions
    (new finding fails, uncommitted shrink fails) and line-shift
    stability of fingerprints;
  * CLI exit codes on a synthetic tree, including the acceptance
    seed (time.time() into a decision-path module);
  * the meta-test: the repo-wide run is clean against the committed
    baseline, which tolerates exactly one finding (PAL403 on ssd_scan,
    the tracked ROADMAP 3(a) debt);
  * PAL-family coverage: fixture pairs per rule, walk determinism,
    packed_gemm acceptance seeds, and the kernel_report CLI contract.
"""
import json
import os
import textwrap

import pytest

from repro.analysis import baseline as bl
from repro.analysis import lint as lint_cli
from repro.analysis.config import LintConfig, default_config
from repro.analysis.core import (SourceModule, all_rule_ids, parse_pragmas,
                                 run_rules)
from repro.analysis.driver import collect_files, run_lint

FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures", "lint")

DECISION_FIXTURES = (
    "det001_bad.py", "det001_good.py",
    "det003_bad.py", "det003_good.py",
    "det004_bad.py", "det004_good.py",
    "det005_bad.py", "det005_good.py",
    "det006_bad.py", "det006_good.py",
)


#: PAL406 budgets for every fixture pallas_call (keyed relpath::entry).
#: pal406_bad deliberately omits ``no_budget`` and mis-registers
#: ``drifted``; everything else matches its modeled bytes exactly so
#: the PAL fixtures stay rule-pure.
FIXTURE_TILE_BUDGETS = {
    "pal401_bad.py::scale": 8192.0,
    "pal401_good.py::scale": 8192.0,
    "pal402_bad.py::gather_like": 8192.0,
    "pal402_good.py::grouped": 8192.0,
    "pal403_bad.py::packed_op": 196608.0,
    "pal403_good.py::packed_op": 196608.0,
    "pal404_bad.py::reduce_rows": 8192.0,
    "pal404_good.py::reduce_rows": 8192.0,
    "pal405_bad.py::copy_op": 8192.0,
    "pal405_bad.py::reduce_rows": 8192.0,
    "pal405_good.py::reduce_rows": 8192.0,
    "pal406_bad.py::drifted": 999999.0,
    "pal406_good.py::tiled": 8192.0,
}


def fixture_config(**overrides):
    base = dict(
        root=FIXDIR,
        paths=(".",),
        decision_modules=DECISION_FIXTURES,
        mask_entrypoints={
            "mask201_bad.py": ("packed_relu", "packed_scale"),
            "mask201_good.py": ("packed_relu", "packed_scale"),
        },
        mask_dispatch={"module": "mask202_bad.py",
                       "modes_const": "MASKED_MODES",
                       "dispatcher": "masked_pool_step", "param": "mode"},
        acc_modules=("acc301_bad.py", "acc301_good.py"),
        masked_kernels={
            "pal403_bad.py": ("packed_op",),
            "pal403_good.py": ("packed_op",),
        },
        tile_budgets=FIXTURE_TILE_BUDGETS,
        tile_nominal_dims={},
    )
    base.update(overrides)
    return LintConfig(**base)


def run_fixture_rules(config=None):
    config = config or fixture_config()
    known = all_rule_ids()
    modules = [SourceModule.load(p, config.root, known)
               for p in collect_files(config)]
    return run_rules(modules, config)


@pytest.fixture(scope="module")
def fixture_findings():
    active, suppressed, pragmas = run_fixture_rules()
    return active, suppressed


def of(findings, rule=None, path=None):
    return [f for f in findings
            if (rule is None or f.rule == rule)
            and (path is None or f.path == path)]


# -------------------------------------------------------------------------
# per-rule fixture pairs
# -------------------------------------------------------------------------

RULE_CASES = [
    # (rule, bad fixture, expected findings, good twin)
    ("DET001", "det001_bad.py", 3, "det001_good.py"),
    ("DET002", "det002_bad.py", 2, "det002_good.py"),
    ("DET003", "det003_bad.py", 3, "det003_good.py"),
    ("DET004", "det004_bad.py", 3, "det004_good.py"),
    ("DET005", "det005_bad.py", 1, "det005_good.py"),
    ("DET006", "det006_bad.py", 2, "det006_good.py"),
    ("JAX101", "jax101_bad.py", 2, "jax101_good.py"),
    ("JAX102", "jax102_bad.py", 2, "jax102_good.py"),
    ("JAX103", "jax103_bad.py", 3, "jax103_good.py"),
    ("MASK201", "mask201_bad.py", 2, "mask201_good.py"),
    ("MASK202", "mask202_bad.py", 1, "mask202_good.py"),
    ("ACC301", "acc301_bad.py", 2, "acc301_good.py"),
    ("PAL401", "pal401_bad.py", 2, "pal401_good.py"),
    ("PAL402", "pal402_bad.py", 1, "pal402_good.py"),
    ("PAL403", "pal403_bad.py", 1, "pal403_good.py"),
    ("PAL404", "pal404_bad.py", 2, "pal404_good.py"),
    ("PAL405", "pal405_bad.py", 2, "pal405_good.py"),
    ("PAL406", "pal406_bad.py", 2, "pal406_good.py"),
]


@pytest.mark.parametrize("rule,bad,expected,good", RULE_CASES,
                         ids=[c[0] for c in RULE_CASES])
def test_rule_fixture_pair(fixture_findings, rule, bad, expected, good):
    active, _ = fixture_findings
    hits = of(active, rule=rule, path=bad)
    assert len(hits) == expected, (
        f"{rule} should fire {expected}x on {bad}, got "
        f"{[f.render() for f in of(active, path=bad)]}")
    # the bad fixture triggers ONLY its own rule (fixtures are rule-pure)
    assert of(active, path=bad) == hits
    # the good twin is completely clean
    assert of(active, path=good) == [], (
        f"good twin {good} must be clean, got "
        f"{[f.render() for f in of(active, path=good)]}")


def test_mask202_good_dispatcher_clean():
    # MASK202 audits one dispatcher module per config; point it at the
    # good twin and assert full mode coverage passes
    cfg = fixture_config(mask_dispatch={
        "module": "mask202_good.py", "modes_const": "MASKED_MODES",
        "dispatcher": "masked_pool_step", "param": "mode"})
    active, _, _ = run_fixture_rules(cfg)
    assert of(active, rule="MASK202") == []


def test_findings_render_rule_and_path(fixture_findings):
    active, _ = fixture_findings
    f = of(active, rule="DET001")[0]
    rendered = f.render()
    assert "DET001" in rendered and "det001_bad.py" in rendered
    assert f.line > 0 and f.context != ""


# -------------------------------------------------------------------------
# pragmas
# -------------------------------------------------------------------------

def test_pragma_suppresses_with_reason(fixture_findings):
    active, suppressed = fixture_findings
    assert of(active, path="pragma_ok.py") == []
    sup = of(suppressed, path="pragma_ok.py")
    assert [f.rule for f in sup] == ["DET002"]


def test_pragma_empty_reason_is_lnt001_and_does_not_suppress(
        fixture_findings):
    active, _ = fixture_findings
    rules = sorted(f.rule for f in of(active, path="pragma_bad.py"))
    # the malformed pragma is flagged AND the underlying violation stays
    assert rules == ["DET002", "LNT001", "LNT002"]


def test_pragma_unused_is_lnt002(fixture_findings):
    active, _ = fixture_findings
    lnt2 = of(active, rule="LNT002", path="pragma_bad.py")
    assert len(lnt2) == 1
    assert "DET002" in lnt2[0].message


def test_parse_pragmas_entries_and_malformed():
    src = textwrap.dedent("""\
        x = 1  # lint: disable=DET001(reason one),DET002(reason two)
        y = 2  # lint: disable=ZZZ999(whatever)
        z = 3  # lint: disable=DET001
        """)
    pragmas, malformed = parse_pragmas(src, known_rules=all_rule_ids())
    assert [(p.line, p.rule, p.reason) for p in pragmas] == [
        (1, "DET001", "reason one"), (1, "DET002", "reason two")]
    problems = {line: msg for line, msg in malformed}
    assert "unknown rule ZZZ999" in problems[2]
    assert "missing a (reason)" in problems[3]


def test_pragma_inside_docstring_is_ignored():
    src = '"""Example: # lint: disable=DET001(not a real pragma)"""\n'
    pragmas, malformed = parse_pragmas(src, known_rules=all_rule_ids())
    assert pragmas == [] and malformed == []


# -------------------------------------------------------------------------
# baseline round-trip + zero-drift
# -------------------------------------------------------------------------

def test_baseline_roundtrip(tmp_path, fixture_findings):
    active, _ = fixture_findings
    path = str(tmp_path / "bl.json")
    bl.save_baseline(path, active)
    loaded = bl.load_baseline(path)
    assert loaded == bl.count_findings(active)
    # identical findings diff clean against their own baseline
    new, stale = bl.diff_baseline(active, loaded)
    assert new == [] and stale == []


def test_baseline_flags_new_and_stale(tmp_path, fixture_findings):
    active, _ = fixture_findings
    path = str(tmp_path / "bl.json")
    bl.save_baseline(path, active[1:])         # one finding not tolerated
    new, stale = bl.diff_baseline(active, bl.load_baseline(path))
    assert [f.fingerprint for f in new] == [active[0].fingerprint]
    # ...and the reverse: a fixed finding leaves a stale entry
    bl.save_baseline(path, active)
    new, stale = bl.diff_baseline(active[1:], bl.load_baseline(path))
    assert new == [] and stale == [active[0].fingerprint]


def test_baseline_version_check(tmp_path):
    path = tmp_path / "bl.json"
    path.write_text(json.dumps({"version": 999, "findings": {}}))
    with pytest.raises(ValueError, match="version"):
        bl.load_baseline(str(path))


def test_fingerprint_survives_line_shift(tmp_path):
    """The baseline keys on scope + normalized text, not line numbers:
    edits above a tolerated finding must not count as drift."""
    mod = tmp_path / "wall.py"
    body = "import time\n\n\ndef took():\n    return time.time()\n"
    mod.write_text(body)
    cfg = LintConfig(root=str(tmp_path), paths=("wall.py",))
    r1 = run_lint(cfg)
    assert [f.rule for f in r1.active] == ["DET002"]
    bl.save_baseline(cfg.abs_baseline(), r1.active)

    mod.write_text("# a comment pushing everything down two lines\n\n"
                   + body)
    r2 = run_lint(cfg)
    assert r2.active[0].line != r1.active[0].line
    assert r2.ok, (r2.new, r2.stale)


# -------------------------------------------------------------------------
# CLI exit codes on a synthetic tree
# -------------------------------------------------------------------------

def _seed_tree(tmp_path, violation=True):
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    clock = "time.time()" if violation else "time.perf_counter()"
    (tmp_path / "src" / "repro" / "timing.py").write_text(
        f"import time\n\n\ndef took(t0):\n    return {clock} - t0\n")
    return tmp_path


def test_cli_check_fails_on_violation_names_rule(tmp_path, capsys):
    root = _seed_tree(tmp_path, violation=True)
    rc = lint_cli.main(["--root", str(root), "--check"])
    captured = capsys.readouterr().out
    assert rc == 1
    assert "DET002" in captured and "timing.py" in captured


def test_cli_clean_tree_exits_zero(tmp_path, capsys):
    root = _seed_tree(tmp_path, violation=False)
    rc = lint_cli.main(["--root", str(root), "--check"])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_cli_update_baseline_then_check_then_stale(tmp_path, capsys):
    root = _seed_tree(tmp_path, violation=True)
    assert lint_cli.main(["--root", str(root), "--update-baseline"]) == 0
    # tolerated by the baseline now
    assert lint_cli.main(["--root", str(root), "--check"]) == 0
    # fixing the violation WITHOUT shrinking the baseline is drift too
    _seed_tree_fix = root / "src" / "repro" / "timing.py"
    _seed_tree_fix.write_text(
        "import time\n\n\ndef took(t0):\n"
        "    return time.perf_counter() - t0\n")
    rc = lint_cli.main(["--root", str(root), "--check"])
    assert rc == 1
    assert "stale" in capsys.readouterr().out


def test_cli_seeded_decision_module_violation(tmp_path, capsys):
    """The ISSUE acceptance seed: time.time() appearing in a
    decision-path module trips DET001 (not just DET002) by path."""
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "simulate.py").write_text(
        "import time\n\n\ndef pick():\n    return time.time()\n")
    rc = lint_cli.main(["--root", str(tmp_path), "--check"])
    captured = capsys.readouterr().out
    assert rc == 1
    assert "DET001" in captured


def test_cli_list_rules(capsys):
    assert lint_cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("DET001", "DET006", "JAX101", "JAX103", "MASK201",
                "MASK202", "ACC301"):
        assert rid in out


def test_cli_json_output(tmp_path, capsys):
    root = _seed_tree(tmp_path, violation=True)
    rc = lint_cli.main(["--root", str(root), "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["new"][0]["rule"] == "DET002"


# -------------------------------------------------------------------------
# meta: the repo itself is clean against the committed baseline
# -------------------------------------------------------------------------

def test_repo_wide_lint_is_clean():
    result = run_lint(default_config())
    assert result.ok, (
        "repo lint must match the committed baseline exactly:\n"
        + "\n".join(f.render() for f in result.new)
        + "\n".join(result.stale))
    # the only tolerated finding is the tracked ROADMAP 3(a) debt:
    # ssd_scan has no in-kernel lane gate yet (flash got its gate in
    # this PR's satellite; ssd is the remaining half)
    assert [(f.rule, f.path, f.context) for f in result.active] == [
        ("PAL403", "src/repro/kernels/ssd_scan.py", "ssd_scan")], (
        "\n".join(f.render() for f in result.active))
    base = bl.load_baseline(default_config().abs_baseline())
    assert list(base) == [result.active[0].fingerprint]
    assert base[result.active[0].fingerprint] == 1


def _toplevel_def_names(path):
    import ast
    with open(path, "r", encoding="utf-8") as f:
        tree = ast.parse(f.read())
    return {n.name for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def test_repo_config_names_real_files():
    """Config rot check: every configured path exists so rules cannot
    silently skip a renamed module."""
    cfg = default_config()
    for rel in (cfg.decision_modules + cfg.acc_modules
                + tuple(cfg.mask_entrypoints)
                + tuple(cfg.masked_kernels)
                + (cfg.mask_dispatch["module"],)):
        assert os.path.exists(os.path.join(cfg.root, rel)), rel


def test_repo_config_names_real_functions():
    """Function-level config rot check: renaming a registered entrypoint
    (e.g. packed_norm) must fail here instead of silently turning the
    rule off for it."""
    cfg = default_config()
    for rel, names in cfg.mask_entrypoints.items():
        defs = _toplevel_def_names(os.path.join(cfg.root, rel))
        for name in names:
            assert name in defs, (
                f"MASK_ENTRYPOINTS registers {rel}:{name} but no such "
                f"top-level def exists")
    for rel, names in cfg.masked_kernels.items():
        defs = _toplevel_def_names(os.path.join(cfg.root, rel))
        for name in names:
            assert name in defs, (
                f"MASKED_KERNELS registers {rel}:{name} but no such "
                f"top-level def exists")
    # donating factories live in the dispatcher module
    packing = os.path.join(cfg.root, cfg.mask_dispatch["module"])
    defs = _toplevel_def_names(packing)
    for name in cfg.donating_factories:
        assert name in defs, (
            f"DONATING_FACTORIES registers {name} but "
            f"{cfg.mask_dispatch['module']} has no such top-level def")
    # tile budgets / nominal dims must point at real kernel files too
    for key in cfg.tile_budgets:
        rel, _, entry = key.partition("::")
        path = os.path.join(cfg.root, rel)
        assert os.path.exists(path), key
        assert entry in _toplevel_def_names(path), key
    for rel in cfg.tile_nominal_dims:
        assert os.path.exists(os.path.join(cfg.root, rel)), rel


# -------------------------------------------------------------------------
# deterministic walk: report bytes must not depend on filesystem order
# -------------------------------------------------------------------------

def _shuffled_tree(tmp_path, name, order):
    """A tree with one pallas kernel + one DET002 violation, created in
    the given file order (os.walk on unsorted filesystems can differ)."""
    root = tmp_path / name
    pkg = root / "src" / "repro"
    pkg.mkdir(parents=True)
    kernel = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n\n\n"
        "def _k(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n\n\n"
        "def tiled(x):\n"
        "    return pl.pallas_call(\n"
        "        _k,\n"
        "        grid=(4, 4),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 512), jnp.float32),\n"
        "    )(x)\n")
    files = {
        "aaa.py": "import time\n\n\ndef t():\n    return time.time()\n",
        "mmm.py": kernel,
        "zzz.py": "import time\n\n\ndef t():\n    return time.time()\n",
    }
    for fn in order:
        (pkg / fn).write_text(files[fn])
    return root


def test_lint_walk_is_deterministic(tmp_path, capsys):
    """Two trees with identical content but shuffled creation order must
    produce byte-identical --json reports (driver sorts the walk)."""
    from repro.analysis import kernel_report as kr_cli

    outs = {"lint": [], "report": []}
    for name, order in (("one", ("zzz.py", "aaa.py", "mmm.py")),
                        ("two", ("mmm.py", "zzz.py", "aaa.py"))):
        root = _shuffled_tree(tmp_path, name, order)
        lint_cli.main(["--root", str(root), "--json"])
        outs["lint"].append(capsys.readouterr().out)
        kr_cli.main(["--root", str(root), "--json"])
        outs["report"].append(capsys.readouterr().out)
    assert outs["lint"][0] == outs["lint"][1]
    assert outs["report"][0] == outs["report"][1]
    # and the finding order inside one report is the sorted path order
    payload = json.loads(outs["lint"][0])
    paths = [f["path"] for f in payload["active"]]
    assert paths == sorted(paths)


# -------------------------------------------------------------------------
# acceptance seeds: kernel-contract bugs in packed_gemm must exit 1
# -------------------------------------------------------------------------

REAL_PACKED_GEMM = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..",
    "src", "repro", "kernels", "packed_gemm.py")
REAL_DECODE_ATTENTION = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..",
    "src", "repro", "kernels", "decode_attention.py")


def _gemm_tree(tmp_path, mutate=None, source=None):
    source = source or REAL_PACKED_GEMM
    pkg = tmp_path / "src" / "repro" / "kernels"
    pkg.mkdir(parents=True)
    with open(source, "r", encoding="utf-8") as f:
        text = f.read()
    if mutate:
        old, new = mutate
        assert old in text, f"seed pattern {old!r} not found"
        text = text.replace(old, new, 1)
    (pkg / os.path.basename(source)).write_text(text)
    return tmp_path


def test_cli_unmutated_packed_gemm_is_clean(tmp_path):
    root = _gemm_tree(tmp_path)
    assert lint_cli.main(["--root", str(root), "--check"]) == 0


def test_cli_seeded_unguarded_accumulator_fails(tmp_path, capsys):
    """ISSUE acceptance seed: breaking the pl.when(ki == 0) init guard
    in packed_gemm's kernel trips PAL404 and exits 1."""
    root = _gemm_tree(tmp_path,
                      mutate=("@pl.when(ki == 0)", "@pl.when(ki == 7)"))
    rc = lint_cli.main(["--root", str(root), "--check"])
    captured = capsys.readouterr().out
    assert rc == 1
    assert "PAL404" in captured and "acc_scr" in captured


def test_cli_seeded_index_map_arity_bug_fails(tmp_path, capsys):
    """ISSUE acceptance seed: an index map that drops a grid index trips
    PAL401 and exits 1."""
    root = _gemm_tree(tmp_path,
                      mutate=("lambda j, i, n, k: (j, i, k)",
                              "lambda j, i, k: (j, i, k)"))
    rc = lint_cli.main(["--root", str(root), "--check"])
    captured = capsys.readouterr().out
    assert rc == 1
    assert "PAL401" in captured


@pytest.mark.parametrize("mutate,rc", [
    (None, 0),
    (("lambda b, j, i, lay, lo, hi: (b, j, 0, 0)",
      "lambda b, j, i: (b, j, 0, 0)"),
     1)])
def test_cli_scalar_prefetch_index_maps(tmp_path, capsys, mutate, rc):
    """A grid spec with scalar prefetch (decode attention's layer index
    and block bounds): its index maps take the grid indices and then the
    prefetched refs, and one that drops the refs trips PAL401."""
    root = _gemm_tree(tmp_path, mutate=mutate, source=REAL_DECODE_ATTENTION)
    assert lint_cli.main(["--root", str(root), "--check"]) == rc
    assert ("PAL401" in capsys.readouterr().out) == bool(rc)


@pytest.mark.parametrize("bound", [
    "S // bs - 1",      # a closure value, not prefetched
    "j",                # a grid index
    "hi[b + 1]"])       # a prefetched ref at a computed index
def test_cli_clamp_by_a_value_not_prefetched_trips_pal402(tmp_path, capsys,
                                                           bound):
    """Decode attention's K map clamps its position block into the
    lane's prefetched bounds ``lo[b]``, ``hi[b]`` (classed ``pruned``);
    a clamp by anything else is no scalar-prefetch pruning and trips
    PAL402."""
    clamp = "jnp.minimum(jnp.maximum(i, lo[b]), hi[b])"
    root = _gemm_tree(tmp_path, source=REAL_DECODE_ATTENTION,
                      mutate=(clamp, clamp.replace("hi[b]", bound)))
    assert lint_cli.main(["--root", str(root), "--check"]) == 1
    out = capsys.readouterr().out
    assert "PAL402" in out and "in_specs[1]" in out


# -------------------------------------------------------------------------
# kernel_report: the pruning-readiness contract
# -------------------------------------------------------------------------

def test_kernel_report_classifies_all_committed_maps():
    """Acceptance criterion: every committed pallas_call index map is
    classified — the GQA h // G maps as affine_div, decode attention's
    position block clamped into prefetched bounds as pruned (its K, V
    and mask maps), everything else affine."""
    from repro.analysis.kernel_report import build_report

    rep = build_report(default_config())
    assert rep["n_kernels"] == 6
    by_entry = {k["entry"]: k for k in rep["kernels"]}
    assert set(by_entry) == {"flash_attention_fwd", "fused_rmsnorm",
                             "packed_rmsnorm", "packed_gemm", "ssd_scan",
                             "_decode_attention"}
    for k in rep["kernels"]:
        for spec in k["operands"]:
            if spec["index_map"] is None:
                assert spec["memory_space"] == "SMEM"
                continue
            for expr, cls in zip(spec["index_map"]["exprs"],
                                 spec["index_map"]["classes"]):
                expected = ("pruned" if "jnp.minimum" in expr else
                            "affine_div" if "//" in expr else "affine")
                assert cls == expected, (k["entry"], expr, cls)
    decode = [s["index_map"]["classification"]
              for s in by_entry["_decode_attention"]["operands"]]
    # q, then K, V and the mask, then the output
    assert decode == ["affine", "pruned", "pruned", "pruned", "affine"]
    flash = by_entry["flash_attention_fwd"]
    kv_classes = [s["index_map"]["classification"]
                  for s in flash["operands"]
                  if s["index_map"] and "h // G" in s["index_map"]["exprs"][1]]
    assert kv_classes == ["affine_div", "affine_div"]


def test_kernel_report_prunability_tracks_lane_gating():
    """flash/packed_gemm/packed_rmsnorm carry lane predicates and affine
    (or affine_div) maps -> prunable; ssd and the unpacked rmsnorm do
    not (the ssd gap is the tracked baseline entry)."""
    from repro.analysis.kernel_report import build_report

    rep = build_report(default_config())
    by_entry = {k["entry"]: k for k in rep["kernels"]}
    assert by_entry["packed_gemm"]["prunable"]
    assert by_entry["packed_rmsnorm"]["prunable"]
    assert by_entry["flash_attention_fwd"]["prunable"]
    assert by_entry["flash_attention_fwd"]["lane_predicate"]
    assert not by_entry["ssd_scan"]["lane_predicate"]
    assert not by_entry["ssd_scan"]["prunable"]
    assert rep["n_prunable"] == 3
    # the traffic model agrees with the registered budgets exactly
    for k in rep["kernels"]:
        assert k["unresolved_dims"] == []
        assert k["bytes_per_grid_step"] == k["tile_budget"]


def test_kernel_report_check_is_clean_on_repo(capsys):
    from repro.analysis import kernel_report as kr_cli

    assert kr_cli.main(["--check"]) == 0
    assert "clean" in capsys.readouterr().out


def test_kernel_report_check_fails_on_seeded_bug(tmp_path, capsys):
    from repro.analysis import kernel_report as kr_cli

    root = _gemm_tree(tmp_path,
                      mutate=("@pl.when(ki == 0)", "@pl.when(ki == 7)"))
    rc = kr_cli.main(["--root", str(root), "--check"])
    captured = capsys.readouterr().out
    assert rc == 1
    assert "PAL404" in captured


def test_kernel_report_out_writes_json(tmp_path, capsys):
    from repro.analysis import kernel_report as kr_cli

    out = tmp_path / "report.json"
    assert kr_cli.main(["--json", "--out", str(out)]) == 0
    stdout_payload = json.loads(capsys.readouterr().out)
    file_payload = json.loads(out.read_text())
    assert stdout_payload == file_payload
    assert file_payload["n_kernels"] == 6
