"""The analysis plane (tools/analyze): rule engine, rules, config,
reporters, CLI, and the runtime lock-order detector.

Tier-1 contract (ISSUE 5 acceptance):
- the full package tree analyzes to ZERO non-waived errors against the
  committed analyze.toml, with at most 10 waivers, each carrying a
  written reason — removing a waiver (or re-adding a banned call, e.g.
  ``time.time()`` in chain/app.py) fails here with a message naming the
  rule, file, and line;
- every rule is proven live against good/bad fixture pairs under
  tests/analyze_fixtures/;
- pragma > waiver > scope precedence holds;
- the JSON reporter emits the FORMATS §11 schema;
- the CELESTIA_RACE=1 detector catches a deliberate ABBA lock-order
  inversion.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import threading

import pytest

from celestia_app_tpu.tools.analyze import (
    default_config_path,
    default_package_root,
    load_config,
    run_analysis,
)
from celestia_app_tpu.tools.analyze.config import (
    AnalyzeConfig,
    ConfigError,
    RuleConfig,
    Waiver,
    config_from_dict,
    parse_toml_subset,
)
from celestia_app_tpu.tools.analyze.report import to_json
from celestia_app_tpu.tools.analyze import racecheck

FIXTURES = os.path.join(os.path.dirname(__file__), "analyze_fixtures")

RULES = [
    "det-wallclock", "det-rng", "det-float", "det-set-iter",
    "det-dict-hash", "except-swallow", "jit-purity", "lock-guard",
    "print-call", "raw-urlopen",
    # the interprocedural family (ISSUE 12)
    "det-reach", "scope-drift", "blocking-under-lock",
    # the effect system (ISSUE 20)
    "xfer-reach", "lock-order", "guarded-by-flow",
]


def _fixture_config() -> AnalyzeConfig:
    """All rules enabled, unscoped — fixtures opt in per file by name.
    The interprocedural rules additionally need roots / a checked
    include list, pointed at the fixture files; ``det-fixture`` is a
    config-only pseudo-rule (never registered, never run) standing in
    for the hand list scope-drift audits."""
    cfg = AnalyzeConfig(exclude=["__pycache__"])
    cfg.rules["det-reach"] = RuleConfig(options={"roots": [
        "det_reach_bad.py::consensus_root",
        "det_reach_good.py::consensus_root",
        "scope_drift_bad.py::reachable_root",
        "scope_drift_good.py::covered_root",
    ]})
    cfg.rules["scope-drift"] = RuleConfig(
        options={"check": ["det-fixture"]})
    cfg.rules["det-fixture"] = RuleConfig(include=[
        "scope_drift_good.py", "det_reach_bad.py", "det_reach_good.py",
    ])
    cfg.rules["xfer-reach"] = RuleConfig(options={"roots": [
        "xfer_reach_bad.py::produce_root",
        "xfer_reach_good.py::produce_root",
    ]})
    return cfg


def _run_fixture(name: str, only: set[str] | None = None):
    return run_analysis(root=FIXTURES, config=_fixture_config(),
                        only_rules=only)


# ---------------------------------------------------------------------------
# the tier-1 gate: the tree itself is clean
# ---------------------------------------------------------------------------


def test_full_tree_zero_unwaived_violations():
    """THE gate: every rule over every package file, the committed
    analyze.toml applied. Any new violation must be fixed, pragma'd with
    a reason comment, or waived in analyze.toml — never ignored."""
    rep = run_analysis()
    assert sorted(rep.rules_run) == sorted(RULES), rep.rules_run
    msgs = [str(v) for v in rep.errors]
    assert not msgs, (
        "analysis plane violations (fix, pragma, or waive with a "
        f"reason):\n" + "\n".join(msgs)
    )


def test_waiver_budget_and_reasons():
    """≤ 10 waivers, every one with a non-empty written reason."""
    cfg = load_config()
    assert len(cfg.waivers) <= 10, [
        (w.rule, w.path) for w in cfg.waivers
    ]
    for w in cfg.waivers:
        assert w.reason.strip(), f"waiver {w.rule}:{w.path} has no reason"


def test_removing_any_waiver_fails_with_named_violation():
    """Each committed waiver is load-bearing: strip it and the analyzer
    must surface at least one error of exactly that rule in exactly that
    path, with a real line number — proving the waiver ledger cannot
    hide dead entries and the gate names rule+file+line on failure."""
    cfg = load_config()
    assert cfg.waivers, "expected at least one committed waiver"
    for i, dropped in enumerate(cfg.waivers):
        stripped = copy.deepcopy(cfg)
        del stripped.waivers[i]
        rep = run_analysis(config=stripped)
        hits = [v for v in rep.errors
                if v.rule == dropped.rule
                and v.path.startswith(dropped.path.split("::")[0])]
        assert hits, (
            f"waiver {dropped.rule}:{dropped.path} matched nothing "
            "after removal — it is stale"
        )
        assert all(v.line > 0 for v in hits)
        # the failure message names rule, file, and line
        assert dropped.rule in str(hits[0]) and dropped.path in str(hits[0])


def test_reintroducing_banned_call_is_caught(tmp_path):
    """A tree that re-adds time.time()/random in chain/app.py (the
    acceptance example) fails under the COMMITTED config's scoping."""
    pkg = tmp_path / "pkg"
    (pkg / "chain").mkdir(parents=True)
    (pkg / "chain" / "app.py").write_text(
        "import random\nimport time\n\n\n"
        "def finalize(txs):\n"
        "    stamp = time.time()\n"
        "    random.shuffle(txs)\n"
        "    return stamp, txs\n"
    )
    rep = run_analysis(root=str(pkg), config=load_config())
    found = {(v.rule, v.path, v.line) for v in rep.errors}
    assert ("det-wallclock", "chain/app.py", 6) in found, found
    assert ("det-rng", "chain/app.py", 7) in found, found


# ---------------------------------------------------------------------------
# every rule proven live: good/bad fixture pairs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", RULES)
def test_rule_fixture_pair(rule):
    stem = rule.replace("-", "_")
    rep = _run_fixture(rule, only={rule})
    by_file: dict[str, list] = {}
    for v in rep.violations:
        by_file.setdefault(v.path, []).append(v)
    bad = by_file.get(f"{stem}_bad.py", [])
    good = by_file.get(f"{stem}_good.py", [])
    assert bad, f"{rule}: bad fixture produced no violation"
    assert all(v.rule == rule for v in bad)
    assert not good, (
        f"{rule}: good fixture flagged: {[str(v) for v in good]}"
    )


def test_bad_fixture_violation_counts():
    """The bad fixtures carry one VIOLATION marker per expected hit;
    the analyzer must find every one of them (no silent under-count)."""
    rep = _run_fixture("all")
    counts: dict[str, int] = {}
    for v in rep.violations:
        counts[v.path] = counts.get(v.path, 0) + 1
    for name in sorted(os.listdir(FIXTURES)):
        if not name.endswith("_bad.py"):
            continue
        with open(os.path.join(FIXTURES, name)) as f:
            expected = f.read().count("VIOLATION")
        assert counts.get(name, 0) >= expected, (
            f"{name}: expected >= {expected} violations, "
            f"got {counts.get(name, 0)}"
        )


# ---------------------------------------------------------------------------
# precedence: pragma > waiver > scope
# ---------------------------------------------------------------------------


def test_pragma_suppresses_entirely():
    rep = _run_fixture("pragma", only={"det-wallclock"})
    hits = [v for v in rep.violations if v.path == "pragma_case.py"]
    assert hits == [], [str(v) for v in hits]


def test_pragma_beats_waiver(tmp_path):
    """A pragma'd line is suppressed (not even counted as waived), and
    the waiver covering the same file then reports stale."""
    (tmp_path / "m.py").write_text(
        "import time\n\n\n"
        "def f():\n"
        "    return time.time()  # lint: disable=det-wallclock\n"
    )
    cfg = AnalyzeConfig(waivers=[
        Waiver(rule="det-wallclock", path="m.py", reason="testing")
    ])
    rep = run_analysis(root=str(tmp_path), config=cfg,
                       only_rules={"det-wallclock"})
    assert not rep.waived
    assert [v.rule for v in rep.errors] == ["stale-waiver"]


def test_waiver_downgrades_and_carries_reason(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n"
    )
    cfg = AnalyzeConfig(waivers=[
        Waiver(rule="det-wallclock", path="m.py",
               reason="fixture: documented exception")
    ])
    rep = run_analysis(root=str(tmp_path), config=cfg,
                       only_rules={"det-wallclock"})
    assert not rep.errors
    assert len(rep.waived) == 1
    assert rep.waived[0].waiver_reason == "fixture: documented exception"


def test_scope_include_and_symbol_scoping(tmp_path):
    src = ("import time\n\n\n"
           "def apply(b):\n"
           "    return time.time()\n\n\n"
           "def gossip():\n"
           "    return time.time()\n")
    (tmp_path / "consensus.py").write_text(src)
    (tmp_path / "tooling.py").write_text(src)
    cfg = AnalyzeConfig(rules={
        "det-wallclock": RuleConfig(include=["consensus.py::apply"]),
    })
    rep = run_analysis(root=str(tmp_path), config=cfg,
                       only_rules={"det-wallclock"})
    hits = {(v.path, v.line) for v in rep.errors}
    # only the apply() body of the included file is in scope
    assert hits == {("consensus.py", 5)}, hits


def test_rule_severity_off_and_warning(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n"
    )
    off = AnalyzeConfig(rules={"det-wallclock": RuleConfig(severity="off")})
    rep = run_analysis(root=str(tmp_path), config=off,
                       only_rules={"det-wallclock"})
    assert rep.violations == [] and "det-wallclock" not in rep.rules_run
    warn = AnalyzeConfig(
        rules={"det-wallclock": RuleConfig(severity="warning")})
    rep = run_analysis(root=str(tmp_path), config=warn,
                       only_rules={"det-wallclock"})
    assert not rep.errors and len(rep.warnings) == 1


# ---------------------------------------------------------------------------
# config loader (the TOML subset) + reporters
# ---------------------------------------------------------------------------


def test_toml_subset_parses_committed_config():
    with open(default_config_path()) as f:
        doc = parse_toml_subset(f.read())
    assert "analyze" in doc and "rules" in doc
    assert isinstance(doc.get("waivers", []), list)
    cfg = config_from_dict(doc)
    assert cfg.rules["print-call"].allow  # the migrated gate allowlists
    assert cfg.rules["raw-urlopen"].allow == ["net/transport.py"]


def test_toml_subset_features_and_errors():
    doc = parse_toml_subset(
        '# comment\n[a.b]\nx = "s"  # trailing\nn = 3\nflag = true\n'
        'arr = [\n  "one",\n  "two",  # c\n]\n[[w]]\nk = "v"\n[[w]]\nk = "u"\n'
    )
    assert doc["a"]["b"] == {"x": "s", "n": 3, "flag": True,
                             "arr": ["one", "two"]}
    assert [w["k"] for w in doc["w"]] == ["v", "u"]
    with pytest.raises(ConfigError):
        parse_toml_subset("x = {inline = 1}\n")
    with pytest.raises(ConfigError):
        config_from_dict({"waivers": [{"rule": "r", "path": "p"}]})


def test_json_report_schema(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n"
    )
    rep = run_analysis(root=str(tmp_path), config=AnalyzeConfig(),
                       only_rules={"det-wallclock"})
    doc = to_json(rep)
    assert doc["version"] == 3
    assert set(doc["summary"]) == {"files_scanned", "rules_run", "errors",
                                   "warnings", "waived", "wall_s",
                                   "cache_hits", "cache_misses"}
    (v,) = doc["violations"]
    assert set(v) == {"rule", "severity", "path", "line", "col",
                      "message", "waived", "waiver_reason", "call_path",
                      "effect"}
    assert v["rule"] == "det-wallclock" and v["path"] == "m.py"
    assert v["line"] == 5 and v["waived"] is False
    assert v["call_path"] == []  # per-file rules carry no chain
    assert v["effect"] is None  # only the effect rules attach payloads
    json.dumps(doc)  # round-trippable


def test_cli_analyze_json_subprocess():
    """The CI surface: `python -m celestia_app_tpu analyze --json` exits
    0 on the committed tree and emits the §11 schema."""
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze", "--json"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == 3 and doc["summary"]["errors"] == 0
    assert doc["summary"]["files_scanned"] > 100


def test_cli_analyze_fails_on_dirty_tree(tmp_path):
    pkg = tmp_path / "pkg" / "chain"
    pkg.mkdir(parents=True)
    (pkg / "app.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--root", str(tmp_path / "pkg")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1
    assert "det-wallclock" in proc.stdout
    assert "chain/app.py:5" in proc.stdout


# ---------------------------------------------------------------------------
# guarded-by annotations: the real structures are actually covered
# ---------------------------------------------------------------------------


def test_known_structures_carry_guarded_by():
    """The satellite's five structures declare their guard, so the
    static rule has real coverage from day one."""
    import ast

    from celestia_app_tpu.tools.analyze.engine import FileContext
    from celestia_app_tpu.tools.analyze.rules_locks import _guarded_attrs

    root = default_package_root()
    expect = {
        ("utils/telemetry.py", "Registry"): {"counters", "timers",
                                             "gauges"},
        ("utils/telemetry.py", "TraceTables"): {"_tables", "_next_index"},
        ("mempool/pool.py", "CATPool"): {"_txs", "_bytes", "_next_seq"},
        ("net/transport.py", "PeerClient"): {"_peers"},
        ("das/daser.py", "DASer"): {"cp", "reports"},
    }
    found: dict[tuple[str, str], set] = {}
    for rel_cls in expect:
        path = os.path.join(root, rel_cls[0])
        ctx = FileContext(rel_cls[0], open(path).read())
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == rel_cls[1]:
                found[rel_cls] = set(_guarded_attrs(node, ctx))
    for key, attrs in expect.items():
        assert attrs <= found.get(key, set()), (key, found.get(key))


# ---------------------------------------------------------------------------
# the runtime half: lock-order inversion detection (CELESTIA_RACE=1)
# ---------------------------------------------------------------------------


@pytest.fixture
def racecheck_installed():
    racecheck.install()
    racecheck.reset()
    try:
        yield
    finally:
        racecheck.uninstall()
        racecheck.reset()


def test_racecheck_catches_abba_inversion(racecheck_installed):
    """A deliberate ABBA setup: T1 takes A then B, T2 takes B then A.
    The detector must record an inversion naming both creation sites —
    without needing the actual deadlock interleaving to strike."""
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def t1():
        with lock_a:
            with lock_b:
                pass

    def t2():
        with lock_b:
            with lock_a:
                pass

    th1 = threading.Thread(target=t1)
    th1.start()
    th1.join()
    th2 = threading.Thread(target=t2)
    th2.start()
    th2.join()
    vios = racecheck.violations()
    assert vios, "ABBA inversion not detected"
    msg = vios[0]["message"]
    assert "lock-order inversion" in msg
    # both creation sites named (same file, two distinct lines)
    assert "test_analyze.py" in vios[0]["first"]
    assert "test_analyze.py" in vios[0]["then"]
    assert vios[0]["first"] != vios[0]["then"]
    # ISSUE 12 triage aid: each thread's acquisition stack rides along
    # (creation-site@acquisition-site entries), in the message too
    for key in ("stack_forward", "stack_reverse"):
        stack = vios[0][key]
        assert len(stack) == 2 and all("@" in s for s in stack), stack
        assert all("test_analyze.py" in s for s in stack)
    assert "acquired" in msg


def test_racecheck_consistent_order_is_clean(racecheck_installed):
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    for _ in range(3):
        with lock_a:
            with lock_b:
                pass
    assert racecheck.violations() == []


def test_racecheck_same_site_instances_not_inversions(racecheck_installed):
    """Two instances created at ONE site (e.g. two CATPools) taken in
    either order are one lock class — not an ABBA report."""
    def make():
        return threading.Lock()  # single creation site for both

    a, b = make(), make()
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert racecheck.violations() == []


def test_racecheck_rlock_reentrancy_no_self_edge(racecheck_installed):
    r = threading.RLock()
    other = threading.Lock()
    with r:
        with r:  # reentrant re-acquire must not record edges
            with other:
                pass
    assert racecheck.violations() == []


def test_racecheck_tracks_condition_and_event(racecheck_installed):
    """Wrapped locks keep working inside Condition/Event (the
    _release_save/_acquire_restore/_is_owned surface)."""
    cond = threading.Condition()
    hit = []

    def waiter():
        with cond:
            hit.append(cond.wait(timeout=5))

    th = threading.Thread(target=waiter)
    th.start()
    ev = threading.Event()
    with cond:
        cond.notify()
    th.join()
    assert hit == [True]
    ev.set()
    assert ev.wait(timeout=1)
    assert racecheck.violations() == []


def test_racecheck_env_hook_in_subprocess():
    """CELESTIA_RACE=1 installs from celestia_app_tpu/__init__ before
    any package lock exists — the chaos/stress subprocess path."""
    code = (
        "import celestia_app_tpu\n"
        "from celestia_app_tpu.tools.analyze import racecheck\n"
        "assert racecheck.installed()\n"
        "from celestia_app_tpu.mempool.pool import CATPool\n"
        "p = CATPool()\n"
        "assert type(p._lock).__name__ == '_TrackedLock', type(p._lock)\n"
        "p.add(b'x' * 8, height=1)\n"
        "assert racecheck.violations() == []\n"
        "print('RACECHECK_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "CELESTIA_RACE": "1", "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "RACECHECK_OK" in proc.stdout


# ---------------------------------------------------------------------------
# the interprocedural family (ISSUE 12): call paths, scope-drift, cache
# ---------------------------------------------------------------------------


def test_det_reach_call_path_content():
    """Interprocedural violations carry the full root→sink chain, in
    the object, the JSON field, and the text rendering."""
    rep = _run_fixture("det-reach", only={"det-reach"})
    hits = [v for v in rep.violations if v.path == "det_reach_bad.py"]
    assert len(hits) == 2, [str(v) for v in hits]
    assert all(v.call_path for v in hits)
    stamp = [v for v in hits if "wall-clock" in v.message][0]
    assert stamp.call_path == ["det_reach_bad.py::consensus_root",
                               "det_reach_bad.py::_stamp"]
    env = [v for v in hits if "environment" in v.message][0]
    assert env.call_path == ["det_reach_bad.py::consensus_root",
                             "det_reach_bad.py::_digest_inputs"]
    assert "call path:" in str(stamp)
    doc = to_json(rep)
    jhits = [v for v in doc["violations"]
             if v["path"] == "det_reach_bad.py"]
    assert jhits and all(v["call_path"] for v in jhits)


def test_det_reach_missing_root_is_error(tmp_path):
    """A configured root that no longer resolves is itself an error —
    the root ledger cannot rot silently."""
    (tmp_path / "m.py").write_text("def f():\n    return 1\n")
    cfg = AnalyzeConfig(rules={"det-reach": RuleConfig(
        options={"roots": ["m.py::gone"]})})
    rep = run_analysis(root=str(tmp_path), config=cfg,
                       only_rules={"det-reach"})
    assert any("not found" in v.message and "m.py::gone" in v.message
               for v in rep.errors), [str(v) for v in rep.errors]


def test_blocking_under_lock_call_path():
    rep = _run_fixture("blocking-under-lock",
                       only={"blocking-under-lock"})
    bad = [v for v in rep.violations
           if v.path == "blocking_under_lock_bad.py"]
    assert len(bad) == 2, [str(v) for v in bad]
    via_helper = [v for v in bad if "sleep" in v.message][0]
    assert via_helper.call_path == [
        "blocking_under_lock_bad.py::Service.slow_update",
        "blocking_under_lock_bad.py::Service._settle",
    ]
    lexical = [v for v in bad if "fsync" in v.message][0]
    assert lexical.line == 18  # reported AT the with statement


def test_jit_purity_transitive_call_path():
    rep = _run_fixture("jit-purity", only={"jit-purity"})
    hits = [v for v in rep.violations
            if v.path == "jit_purity_bad.py" and v.call_path]
    assert hits, "transitive closure produced nothing"
    (t,) = [v for v in hits if "transitively reached" in v.message]
    assert t.call_path == ["jit_purity_bad.py::extend_transitive",
                           "jit_purity_bad.py::_helper_scale"]


def test_scope_drift_fixture_pair_names_file():
    rep = _run_fixture("scope-drift", only={"scope-drift"})
    bad = [v for v in rep.violations if v.path == "scope_drift_bad.py"]
    good = [v for v in rep.violations
            if v.path == "scope_drift_good.py"]
    assert len(bad) == 1 and not good, [str(v) for v in rep.violations]
    assert "[rules.det-fixture]" in bad[0].message
    assert bad[0].call_path  # the chain that makes it consensus


@pytest.mark.parametrize("rid,entry", [
    ("det-wallclock", "wire/"),
    ("det-float", "da/"),
    ("det-rng", "chain/app.py"),
    ("det-set-iter", "das/packs.py"),
])
def test_scope_drift_deleting_committed_entry_fails(rid, entry):
    """THE anti-rot gate (acceptance): strip one include entry from the
    committed config and scope-drift must fail naming a file that entry
    covered — every hand-list entry is load-bearing."""
    cfg = load_config()
    assert entry in cfg.rule(rid).include
    cfg.rule(rid).include.remove(entry)
    rep = run_analysis(config=cfg, only_rules={"scope-drift"})
    hits = [v for v in rep.errors if v.rule == "scope-drift"
            and v.path.startswith(entry.split("::")[0])
            and f"[rules.{rid}]" in v.message]
    assert hits, (rid, entry, [str(v) for v in rep.errors][:5])
    assert all(v.call_path for v in hits)


def test_scopes_report_audit_surface():
    """`analyze --scopes` material: the computed set names the known
    consensus files, and the committed lists carry no dead entries."""
    from celestia_app_tpu.tools.analyze.taint import scopes_report

    rep = run_analysis()
    assert rep.program is not None
    text = scopes_report(rep.program, load_config())
    assert "consensus-reachable:" in text
    for expected in ("chain/app.py", "da/eds.py", "wire/txpb.py",
                     "das/packs.py", "[rules.det-wallclock]"):
        assert expected in text, expected
    assert "unused include entries" not in text, text
    assert "MISSING ROOT" not in text


def test_cache_warm_identity_and_single_file_invalidation(tmp_path):
    """The incremental cache (ISSUE 12 satellite): a warm run is
    byte-identical to a fresh uncached run, and editing one file
    re-derives exactly that file."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    (pkg / "n.py").write_text("def g():\n    return 1\n")
    cache = str(tmp_path / "cache.json")
    cfg = AnalyzeConfig()

    def norm(rep):
        doc = to_json(rep)
        for k in ("wall_s", "cache_hits", "cache_misses"):
            doc["summary"].pop(k)
        return json.dumps(doc, sort_keys=True)

    cold = run_analysis(root=str(pkg), config=cfg, cache=cache)
    assert cold.cache_misses == 2 and cold.cache_hits == 0
    warm = run_analysis(root=str(pkg), config=cfg, cache=cache)
    assert warm.cache_misses == 0 and warm.cache_hits == 2
    fresh = run_analysis(root=str(pkg), config=cfg)
    assert norm(warm) == norm(cold) == norm(fresh)
    # single-file edit: only that file re-derives, results stay honest
    (pkg / "n.py").write_text(
        "import time\n\n\ndef g():\n    return time.time()\n")
    edited = run_analysis(root=str(pkg), config=cfg, cache=cache)
    assert edited.cache_misses == 1 and edited.cache_hits == 1
    fresh2 = run_analysis(root=str(pkg), config=cfg)
    assert norm(edited) == norm(fresh2)
    assert any(v.path == "n.py" for v in edited.errors)
    # parse errors are synthetic, not a registered rule — they must
    # survive warm runs too
    (pkg / "n.py").write_text("def broken(:\n")
    cold3 = run_analysis(root=str(pkg), config=cfg, cache=cache)
    warm3 = run_analysis(root=str(pkg), config=cfg, cache=cache)
    assert warm3.cache_misses == 0
    assert norm(warm3) == norm(cold3)
    assert any(v.rule == "parse-error" for v in warm3.errors)


def test_cache_invalidated_by_config_change(tmp_path):
    (tmp_path / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    cache = str(tmp_path / "cache.json")
    run_analysis(root=str(tmp_path), config=AnalyzeConfig(),
                 cache=cache, only_rules={"det-wallclock"})
    # a different config (severity flip) must not reuse entries
    warn = AnalyzeConfig(
        rules={"det-wallclock": RuleConfig(severity="warning")})
    rep = run_analysis(root=str(tmp_path), config=warn, cache=cache,
                       only_rules={"det-wallclock"})
    assert rep.cache_hits == 0 and rep.cache_misses == 1
    assert not rep.errors and len(rep.warnings) == 1


def test_cache_namespaces_rule_sets_side_by_side(tmp_path):
    """Alternating run shapes (full sweep vs --rule dev loop) keep
    separate warm slots — one must not evict the other."""
    (tmp_path / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    cache = str(tmp_path / "cache.json")
    cfg = AnalyzeConfig()
    run_analysis(root=str(tmp_path), config=cfg, cache=cache)
    run_analysis(root=str(tmp_path), config=cfg, cache=cache,
                 only_rules={"det-wallclock"})
    full = run_analysis(root=str(tmp_path), config=cfg, cache=cache)
    dev = run_analysis(root=str(tmp_path), config=cfg, cache=cache,
                       only_rules={"det-wallclock"})
    assert full.cache_misses == 0 and dev.cache_misses == 0


def test_cli_rule_comma_list_and_unknown_exit_2(tmp_path):
    # chain/app.py so the committed config's det-wallclock scope applies
    (tmp_path / "chain").mkdir()
    (tmp_path / "chain" / "app.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # unknown rule: exit 2, registry on stderr, nothing analyzed
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--root", str(tmp_path), "--rule", "bogus,det-wallclock"],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "unknown rule(s): bogus" in proc.stderr
    assert "det-reach" in proc.stderr  # the registry listing
    # EVERY unknown name reports at once — one round-trip to a clean
    # command line, not one error per retry
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--root", str(tmp_path),
         "--rule", "bogus1,det-wallclock,bogus2"],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "unknown rule(s): bogus1, bogus2" in proc.stderr
    # comma-separated list runs both rules
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--root", str(tmp_path), "--no-cache", "--json",
         "--rule", "det-wallclock,det-rng"],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["summary"]["rules_run"] == ["det-rng", "det-wallclock"]


# ---------------------------------------------------------------------------
# the effect system (ISSUE 20): xfer-reach, lock-order, guarded-by-flow
# ---------------------------------------------------------------------------

EFFECT_RULES = {"xfer-reach", "lock-order", "guarded-by-flow"}


def test_xfer_reach_call_path_and_effect_payload():
    """Every finding carries the root→sink chain and a typed effect
    payload; the good fixture's raw sink is NOT reachable from the
    configured root — the rule proves reachability, not file greps."""
    rep = _run_fixture("xfer-reach", only={"xfer-reach"})
    bad = [v for v in rep.violations if v.path == "xfer_reach_bad.py"]
    assert len(bad) == 3, [str(v) for v in bad]
    assert {v.effect["kind"] for v in bad} == {
        "h2d-raw", "d2h-raw", "asarray"}
    for v in bad:
        assert v.call_path[0] == "xfer_reach_bad.py::produce_root"
        assert v.effect["root"] == "xfer_reach_bad.py::produce_root"
        assert v.effect["sink"] == v.call_path[-1]
        assert "obs.xfer" in v.message  # the fix is named in the text
    assert not [v for v in rep.violations
                if v.path == "xfer_reach_good.py"]


def test_xfer_reach_empty_and_missing_roots_are_errors(tmp_path):
    """An effect rule that silently checks nothing is worse than none:
    an empty root set and a root that no longer resolves both fail."""
    (tmp_path / "m.py").write_text("def f():\n    return 1\n")
    cfg = AnalyzeConfig(rules={"xfer-reach": RuleConfig()})
    rep = run_analysis(root=str(tmp_path), config=cfg,
                       only_rules={"xfer-reach"})
    assert any("no roots" in v.message for v in rep.errors), (
        [str(v) for v in rep.errors])
    cfg = AnalyzeConfig(rules={"xfer-reach": RuleConfig(
        options={"roots": ["m.py::gone"]})})
    rep = run_analysis(root=str(tmp_path), config=cfg,
                       only_rules={"xfer-reach"})
    assert any("not found" in v.message and "m.py::gone" in v.message
               for v in rep.errors), [str(v) for v in rep.errors]


@pytest.mark.parametrize("entry", [
    "da/edscache.py::cache_key",
    "ops/rs.py::extend_square_np",
    "ops/polar.py::reliability",
    "parallel/mesh.py::make_mesh",
])
def test_xfer_reach_deleting_allow_entry_fails(entry):
    """The anti-rot matrix, extended to the new allow list: every
    committed xfer-reach barrier is load-bearing — strip one and the
    rule surfaces an error naming a sink in that entry's file."""
    cfg = load_config()
    assert entry in cfg.rule("xfer-reach").allow
    cfg.rule("xfer-reach").allow.remove(entry)
    rep = run_analysis(config=cfg, only_rules={"xfer-reach"})
    target = entry.split("::")[0]
    hits = [v for v in rep.errors
            if v.rule == "xfer-reach" and v.path == target]
    assert hits, (entry, [str(v) for v in rep.errors][:5])
    assert all(v.call_path and v.effect for v in hits)


def test_lock_order_reports_both_acquisition_paths():
    """One ABBA cycle = one finding carrying BOTH full acquisition
    chains — the lexical nesting half and the call-graph half."""
    rep = _run_fixture("lock-order", only={"lock-order"})
    (v,) = [x for x in rep.violations if x.path == "lock_order_bad.py"]
    a = "lock_order_bad.py::order_lock_a"
    b = "lock_order_bad.py::order_lock_b"
    assert v.effect["cycle"] == [a, b]
    assert v.effect["ab"]["chain"] == ["lock_order_bad.py::forward"]
    assert v.effect["ba"]["chain"] == ["lock_order_bad.py::reverse",
                                       "lock_order_bad.py::_grab_a"]
    assert "forward" in v.message and "_grab_a" in v.message
    assert not v.waived


def test_lock_order_ledger_waives_stale_and_unparseable():
    """A ledger entry naming the cycle's two locks downgrades it to
    waived (reason attached); an entry matching nothing and an entry
    that does not parse are both errors — the inversion ledger cannot
    rot in either direction."""
    a = "lock_order_bad.py::order_lock_a"
    b = "lock_order_bad.py::order_lock_b"
    cfg = _fixture_config()
    cfg.rules["lock-order"] = RuleConfig(options={"ledger": [
        f"{b} <-> {a} : fixture: deliberate ABBA pair"]})
    rep = run_analysis(root=FIXTURES, config=cfg,
                       only_rules={"lock-order"})
    waived = [v for v in rep.waived if v.rule == "lock-order"]
    assert len(waived) == 1  # entry order is insensitive (b <-> a)
    assert waived[0].waiver_reason == "fixture: deliberate ABBA pair"
    assert not [v for v in rep.errors if v.rule == "lock-order"]
    cfg.rules["lock-order"] = RuleConfig(options={"ledger": [
        f"{a} <-> {b} : fixture: deliberate ABBA pair",
        "x.py::gone_a <-> x.py::gone_b : fixture: stale entry",
        "not a ledger entry",
    ]})
    rep = run_analysis(root=FIXTURES, config=cfg,
                       only_rules={"lock-order"})
    msgs = [v.message for v in rep.errors]
    assert any("stale lock-order ledger entry" in m and "gone_a" in m
               for m in msgs), msgs
    assert any("unparseable lock-order ledger entry" in m
               for m in msgs), msgs


def test_guarded_by_flow_call_path_and_payload():
    rep = _run_fixture("guarded-by-flow", only={"guarded-by-flow"})
    (v,) = [x for x in rep.violations
            if x.path == "guarded_by_flow_bad.py"]
    assert v.line == 16  # AT the unguarded call site
    assert v.call_path == [
        "guarded_by_flow_bad.py::Counters.refresh",
        "guarded_by_flow_bad.py::Counters._bump_locked",
    ]
    assert v.effect["attr"] == "_totals"
    assert v.effect["lock"].endswith("Counters._lock")
    assert "_bump_locked" in v.message


def test_changed_filter_and_full_tree_effect_gate(tmp_path):
    """Satellite: the tier-1 gate and the dev loop in one test.
    (a) The three effect rules run over the FULL package tree with
    zero unwaived findings — xfer-reach proving no unledgered host-
    materialization sink is reachable from any warmed root. (b) The
    `--changed` flag filters the report to violations touching
    git-changed files (the full tree still feeds the call graph)."""
    rep = run_analysis(only_rules=set(EFFECT_RULES))
    assert sorted(rep.rules_run) == sorted(EFFECT_RULES)
    assert [str(v) for v in rep.errors] == []
    # not even waived: the warmed produce path is residency-clean
    assert [v for v in rep.violations if v.rule == "xfer-reach"] == []

    pkg = tmp_path / "pkg"
    (pkg / "chain").mkdir(parents=True)
    (pkg / "chain" / "app.py").write_text("def f():\n    return 1\n")
    (pkg / "chain" / "state.py").write_text(
        "import time\n\n\ndef g():\n    return time.time()\n")
    git = ["git", "-C", str(tmp_path)]
    for argv in (git + ["init", "-q"],
                 git + ["add", "-A"],
                 git + ["-c", "user.name=t", "-c", "user.email=t@t",
                        "commit", "-qm", "seed"]):
        subprocess.run(argv, check=True, timeout=30,
                       capture_output=True)
    # state.py's violation is COMMITTED (not changed); edit app.py
    (pkg / "chain" / "app.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--root", str(pkg), "--changed", "--json", "--no-cache",
         "--rule", "det-wallclock"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert {v["path"] for v in doc["violations"]} == {"chain/app.py"}


def test_lock_order_ledger_matches_racecheck_waivers():
    """THE cross-check (satellite): one committed ledger, two
    detectors. Every waived static cycle corresponds 1:1 to a
    [rules.lock-order] ledger entry (unmatched entries are stale
    errors, so the reverse direction is pinned by the gate), and the
    runtime racecheck loads exactly the same entries from the same
    section — the two detectors cannot silently disagree about the
    set of known inversions."""
    cfg = load_config()
    entries = [str(e) for e in
               cfg.rule("lock-order").options.get("ledger", [])]
    rep = run_analysis(only_rules={"lock-order"})
    cycles = [v for v in rep.violations if v.rule == "lock-order"
              and v.effect and "cycle" in v.effect]
    waived = [v for v in cycles if v.waived]
    stale = [v for v in rep.errors
             if "stale lock-order ledger" in v.message]
    assert len(waived) == len(entries) and not stale, (
        [str(v) for v in cycles], entries)
    try:
        n = racecheck.load_waiver_ledger_from_config()
        assert n == len(entries)
        assert racecheck.waiver_ledger() == entries
    finally:
        racecheck.set_waiver_ledger([])


def test_racecheck_waiver_ledger_covers_runtime_abba(racecheck_installed):
    """The runtime half consumes the SAME entry format, matching by
    creation-site file pair: an installed entry downgrades a live ABBA
    inversion to waived — excluded from violations() so chaos/stress
    assertions stay strict — while waived_violations() keeps the
    forensic record; an unparseable entry refuses to install."""
    with pytest.raises(ValueError):
        racecheck.set_waiver_ledger(["not a ledger entry"])
    try:
        racecheck.set_waiver_ledger([
            "tests/test_analyze.py <-> tests/test_analyze.py"
            " : fixture: the deliberate ABBA below"])
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def t1():
            with lock_a:
                with lock_b:
                    pass

        def t2():
            with lock_b:
                with lock_a:
                    pass

        for fn in (t1, t2):
            th = threading.Thread(target=fn)
            th.start()
            th.join()
        assert racecheck.violations() == []  # waived: excluded
        w = racecheck.waived_violations()
        assert len(w) == 1 and w[0]["waived"] is True
        assert w[0]["waiver_reason"] == (
            "fixture: the deliberate ABBA below")
        assert racecheck.violations(include_waived=True) == w
    finally:
        racecheck.set_waiver_ledger([])


def test_effect_rules_warm_cold_identity(tmp_path):
    """Interprocedural effect rules are never cached: a warm run
    re-links and re-derives them from cached fragments, byte-identical
    to a cold run and to a fresh uncached one."""
    cache = str(tmp_path / "cache.json")
    cfg = _fixture_config()

    def norm(rep):
        doc = to_json(rep)
        for k in ("wall_s", "cache_hits", "cache_misses"):
            doc["summary"].pop(k)
        return json.dumps(doc, sort_keys=True)

    cold = run_analysis(root=FIXTURES, config=cfg, cache=cache,
                        only_rules=set(EFFECT_RULES))
    assert cold.cache_misses > 0
    warm = run_analysis(root=FIXTURES, config=cfg, cache=cache,
                        only_rules=set(EFFECT_RULES))
    assert warm.cache_misses == 0 and warm.cache_hits > 0
    fresh = run_analysis(root=FIXTURES, config=cfg,
                         only_rules=set(EFFECT_RULES))
    assert norm(warm) == norm(cold) == norm(fresh)


def test_cache_invalidated_by_rule_set_upgrade(tmp_path, monkeypatch):
    """Satellite (upgrade bugfix): the cache key folds in a sha256
    over every tools/analyze/*.py source (effects.py included), so
    adding or editing ANY rule module invalidates stale per-file
    entries instead of serving results computed by the old rules."""
    from celestia_app_tpu.tools.analyze import cache as cache_mod

    src_dir = os.path.dirname(cache_mod.__file__)
    assert "effects.py" in os.listdir(src_dir)  # the hash covers it
    (tmp_path / "m.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    cache = str(tmp_path / "cache.json")
    cfg = AnalyzeConfig()
    run_analysis(root=str(tmp_path), config=cfg, cache=cache)
    warm = run_analysis(root=str(tmp_path), config=cfg, cache=cache)
    assert warm.cache_misses == 0 and warm.cache_hits == 1
    old = cache_mod.rules_source_hash()
    monkeypatch.setattr(cache_mod, "rules_source_hash",
                        lambda: old + "-rule-set-upgraded")
    rep = run_analysis(root=str(tmp_path), config=cfg, cache=cache)
    assert rep.cache_hits == 0 and rep.cache_misses == 1
    assert any(v.rule == "det-wallclock" for v in rep.errors)


def test_cli_effects_prints_symbol_summary():
    """`analyze --effects <qualname>` prints the computed summary:
    clean residency for the ledger-routed CMT device hash, plus its
    transitive lock acquisitions with full chains."""
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--effects", "da/cmt.py::_hash_symbols"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "effect summary for da/cmt.py::_hash_symbols" in proc.stdout
    assert "host: clean" in proc.stdout
    assert "acquires:" in proc.stdout
    assert "obs/xfer.py::_totals_lock" in proc.stdout
    # an unresolvable symbol degrades to a message, not a traceback
    proc = subprocess.run(
        [sys.executable, "-m", "celestia_app_tpu", "analyze",
         "--effects", "no/such.py::symbol"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "not found in the call graph" in proc.stdout


# ---------------------------------------------------------------------------
# bench surface
# ---------------------------------------------------------------------------


def test_full_tree_wall_time_budget():
    """The tier-1/pre-commit cost must stay interactive: < 10 s on CPU
    cold. Priced in this process's CPU seconds, not wall: the analysis is
    one thread (5.6 s on an idle core), and under `pytest -n 6` the wall
    clock measures the neighbours — it read 12.4 s beside the TPU-compile
    tests."""
    import time

    cpu0 = time.process_time()
    rep = run_analysis()
    cpu_s = time.process_time() - cpu0
    assert rep.cache_misses and cpu_s < 10.0, f"analyze took {cpu_s:.1f}s"
