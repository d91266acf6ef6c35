"""sonata-lint (tools/analysis): the analysis framework's own tests.

Two halves, per the lane's contract:

1. **Fixture detection** — each pass must report the violations seeded
   in ``tests/analysis_fixtures/`` (lock cycles, blocked holds,
   host-syncs, knob drift, asymmetric metric registration) with
   actionable file:line diagnostics.
2. **Clean real tree** — ``run_all()`` over the repo reports zero
   un-allowlisted findings and zero allowlist errors (the exact
   condition the CI "static analysis" step gates on).

Plus the allowlist semantics: stale anchors and unused entries are
errors, never silent.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # `pytest` invoked without `python -m`
    sys.path.insert(0, str(REPO))

from tools.analysis import PASSES, run_all  # noqa: E402
from tools.analysis import (  # noqa: E402
    failpoints,
    hostsync,
    knobs,
    lockorder,
    metricsdoc,
    sharedstate,
    threadlife,
    yieldlock,
)
from tools.analysis.core import (  # noqa: E402
    Allowlist,
    AnalysisContext,
    parse_mini_toml,
    render_report,
)

FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"


def fixture_ctx(*files: str, docs=()) -> AnalysisContext:
    return AnalysisContext.build(FIXTURES, code_roots=list(files),
                                 doc_paths=list(docs))


def codes(diags):
    return {d.code for d in diags}


# ---------------------------------------------------------------------------
# pass 1: lock-order
# ---------------------------------------------------------------------------

def test_lock_cycle_detected():
    diags = lockorder.run(fixture_ctx("fx_lock_cycle.py"))
    cycles = [d for d in diags if d.code == "lock-cycle"]
    assert cycles, "seeded A→B / B→A cycle not reported"
    assert "A_LOCK" in cycles[0].message and "B_LOCK" in cycles[0].message
    assert cycles[0].file == "fx_lock_cycle.py"


def test_blocked_holds_detected_with_lines():
    ctx = fixture_ctx("fx_blocked_hold.py")
    diags = [d for d in lockorder.run(ctx)
             if d.code == "blocking-under-lock"]
    by_line = {d.line: d.message for d in diags}
    src = (FIXTURES / "fx_blocked_hold.py").read_text().splitlines()

    def line_of(snippet):
        return next(i for i, l in enumerate(src, 1) if snippet in l)

    assert line_of("_queue.get()") in by_line          # unbounded get
    assert line_of("open(path)") in by_line            # file I/O
    result_lines = [i for i, l in enumerate(src, 1) if "fut.result()" in l]
    assert result_lines[0] in by_line                  # future result
    # bounded / nowait variants are NOT findings
    assert line_of("timeout=0.1") not in by_line
    assert line_of("get_nowait") not in by_line
    # a function that merely DEFINES a blocking callback is not itself
    # blocking: calling it under a lock is clean (review-pass fix — the
    # nested def's facts must not bleed into its definer's summary)
    assert line_of("defines_callback_only()  # NOT") not in by_line
    assert result_lines[1] not in by_line  # the nested body itself


def test_lock_pass_reports_nothing_on_clean_fixture():
    diags = lockorder.run(fixture_ctx("fx_knobs_a.py"))
    assert diags == []


# ---------------------------------------------------------------------------
# pass 2: host-sync
# ---------------------------------------------------------------------------

def test_hostsync_traced_violations_detected():
    diags = hostsync.run(fixture_ctx("fx_host_sync.py"))
    got = codes(diags)
    assert "tracer-to-python" in got       # float()/np.asarray/.item()
    assert "unstable-iteration" in got     # set iteration in traced code
    assert "host-sync-on-dispatch-path" in got  # device_get after factory
    traced = [d for d in diags if d.code == "tracer-to-python"]
    assert len(traced) == 3  # float(), np.asarray(), .item()
    assert all(d.file == "fx_host_sync.py" for d in diags)
    # the clean jitted `run` produced nothing
    assert not any("run" in d.message.split(":")[0] for d in diags)


def test_hostsync_clean_on_lock_fixture():
    assert hostsync.run(fixture_ctx("fx_lock_cycle.py")) == []


# ---------------------------------------------------------------------------
# pass 3: knobs
# ---------------------------------------------------------------------------

def test_knob_drift_detected():
    ctx = fixture_ctx("fx_knobs_a.py", "fx_knobs_b.py",
                      docs=["fx_docs.md"])
    diags = knobs.run(ctx)
    by_code = {}
    for d in diags:
        by_code.setdefault(d.code, []).append(d)
    undocumented = by_code.get("undocumented-knob", [])
    assert any("SONATA_FX_UNDOCUMENTED" in d.message for d in undocumented)
    assert not any("SONATA_FX_DOCUMENTED" in d.message
                   for d in undocumented)
    split = by_code.get("split-default", [])
    assert any("SONATA_FX_SPLIT" in d.message for d in split)
    stale = by_code.get("stale-doc-knob", [])
    assert any("SONATA_FX_GHOST" in d.message for d in stale)
    assert all(d.file == "fx_docs.md" for d in stale)


# ---------------------------------------------------------------------------
# pass 4: metrics
# ---------------------------------------------------------------------------

def test_metric_asymmetry_and_doc_drift_detected():
    ctx = fixture_ctx("fx_metrics.py", docs=["fx_docs.md"])
    diags = metricsdoc.run(ctx)
    got = codes(diags)
    assert "unrecorded-series" in got   # labels() with no bookkeeping
    assert "missing-unregister" in got  # no unregister_* in the module
    ghost = [d for d in diags if d.code == "unknown-doc-metric"]
    assert any("sonata_fx_ghost_metric" in d.message for d in ghost)
    # the registered family itself is known → not reported
    assert not any("sonata_fx_leaky" in d.message for d in ghost)


def test_metric_loop_registered_families_resolve():
    """Family names flowing through a loop variable from a literal
    table (the scope.py registration idiom) must be resolvable — no
    allowlisting — while true ghosts keep being reported."""
    ctx = fixture_ctx("fx_metrics_loop.py", docs=["fx_docs.md"])
    literals, _patterns = metricsdoc.registered_families(ctx)
    assert {"sonata_fx_loop_alpha", "sonata_fx_loop_beta",
            "sonata_fx_loop_gamma"} <= set(literals)
    diags = metricsdoc.run(ctx)
    ghost = [d for d in diags if d.code == "unknown-doc-metric"]
    assert not any("sonata_fx_loop" in d.message for d in ghost), \
        "loop-registered families must not read as doc ghosts"
    # the seeded ghost in the shared doc fixture is still a finding
    assert any("sonata_fx_ghost_metric" in d.message for d in ghost)


# ---------------------------------------------------------------------------
# pass 5: failpoints
# ---------------------------------------------------------------------------

def test_failpoint_registry_parity_detected():
    ctx = fixture_ctx("fx_failpoints.py", docs=["fx_docs.md"])
    diags = failpoints.run(ctx)
    unknown = [d for d in diags if d.code == "unknown-site"]
    # typo'd fire(), typo'd arm_spec() site prefix, typo'd doc example
    assert any("fx.typo" in d.message
               and d.file == "fx_failpoints.py" for d in unknown)
    assert any("fx.spec_typo" in d.message for d in unknown)
    assert any("fx.doc_typo" in d.message
               and d.file == "fx_docs.md" for d in unknown)
    # the registered site and the grammar template are NOT findings
    assert not any("'fx.good'" in d.message for d in unknown)
    assert not any("'site'" in d.message for d in unknown), \
        "grammar template SONATA_FAILPOINTS=site:mode[...] must be skipped"
    # no tests/tools under the fixture root → every site unexercised
    unex = [d for d in diags if d.code == "unexercised-site"]
    assert {s for d in unex for s in ("fx.good", "fx.undocumented")
            if s in d.message} == {"fx.good", "fx.undocumented"}
    # fx.undocumented appears nowhere in the fixture docs
    undoc = [d for d in diags if d.code == "undocumented-site"]
    assert any("fx.undocumented" in d.message for d in undoc)
    assert not any("'fx.good'" in d.message for d in undoc)


def test_failpoint_pass_ignores_registryless_tree():
    assert failpoints.run(fixture_ctx("fx_lock_cycle.py")) == []


def test_failpoint_exercised_requires_arming_not_substring(tmp_path):
    # the invariant must not be vacuous for common site names: an
    # unrelated identifier containing the site ("warmup_and_mark_ready")
    # or a bare string constant must NOT vouch; a fire/arm/arm_spec
    # literal or a spec-shaped string (HTTP ?arm=, env value) must
    (tmp_path / "reg.py").write_text(
        'SITES = ("warmup", "pool.route", "metrics.scrape", "phonemize")\n',
        encoding="utf-8")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_x.py").write_text(
        "def warmup_and_mark_ready():\n"
        "    return 'warmup'\n"
        "def test_route(arm):\n"
        "    arm('pool.route', 'error')\n"
        "def test_scrape(http_get):\n"
        "    http_get('/debug/failpoints?arm=metrics.scrape:error:1')\n"
        "def test_env(monkeypatch):\n"
        "    monkeypatch.setenv('SONATA_FAILPOINTS', 'phonemize:hang')\n",
        encoding="utf-8")
    ctx = AnalysisContext.build(tmp_path, code_roots=["reg.py"],
                                doc_paths=[])
    unex = {d.message.split("'")[1] for d in failpoints.run(ctx)
            if d.code == "unexercised-site"}
    assert "warmup" in unex, "substring/bare-constant hits must not vouch"
    assert "pool.route" not in unex      # arm() literal
    assert "metrics.scrape" not in unex  # HTTP ?arm= spec string
    assert "phonemize" not in unex       # SONATA_FAILPOINTS env value


# ---------------------------------------------------------------------------
# allowlist semantics
# ---------------------------------------------------------------------------
# the v2 resolver: the PR-17 false cycle, un-renamed
# ---------------------------------------------------------------------------

def test_pr17_false_cycle_fixture_green_unrenamed():
    """Four classes sharing the natural name ``snapshot()`` — the exact
    shape bare-name resolution manufactured a deadlock from (and that
    forced the PR 12/17 ``view()``/``mesh_view()``/``debug_doc``
    renames) — must produce NO finding and need NO allowlist entry."""
    diags = lockorder.run(fixture_ctx("fx_false_cycle.py"))
    assert diags == [], "\n".join(d.format() for d in diags)


def test_real_tree_keeps_natural_snapshot_names():
    """The PR 12/17 defensive renames stay reverted: the mesh, tenancy
    and placement planes all expose ``snapshot()``, and none of the
    dodge-names survive anywhere in the package."""
    import re
    serving = REPO / "sonata_tpu" / "serving"
    for mod, cls in (("mesh.py", "MeshRouter"), ("tenancy.py", None),
                     ("placement.py", None)):
        src = (serving / mod).read_text(encoding="utf-8")
        assert re.search(r"^    def snapshot\(self\)", src, re.M), \
            f"{mod}: snapshot() missing"
    for mod in serving.glob("*.py"):
        src = mod.read_text(encoding="utf-8")
        for dodge in ("mesh_view", "debug_doc", "placement_view"):
            assert dodge not in src, f"{mod.name}: {dodge} survived"


# ---------------------------------------------------------------------------
# pass 6: yield-lock
# ---------------------------------------------------------------------------

def test_yield_under_lock_detected():
    diags = yieldlock.run(fixture_ctx("fx_yield_lock.py"))
    assert codes(diags) == {"yield-under-lock"}
    assert len(diags) == 1
    d = diags[0]
    assert "Ring._lock" in d.message
    # anchored at the yield, block-scoped to the with statement
    assert d.block_line is not None and d.block_line < d.line


def test_yield_after_release_and_span_are_clean():
    """The near misses: copy-release-yield, and a call-shaped context
    manager (trace span) — neither is a finding."""
    diags = yieldlock.run(fixture_ctx("fx_yield_lock.py"))
    lines = {d.line for d in diags}
    src = (FIXTURES / "fx_yield_lock.py").read_text().splitlines()
    for i, text in enumerate(src, 1):
        if "yield item" in text and i not in lines:
            continue  # a clean yield
    # exactly the one seeded positive
    assert len(lines) == 1


# ---------------------------------------------------------------------------
# pass 7: shared-state
# ---------------------------------------------------------------------------

def test_unguarded_shared_write_detected():
    diags = sharedstate.run(fixture_ctx("fx_shared_state.py"))
    assert codes(diags) == {"unguarded-shared-write"}
    assert len(diags) == 1
    d = diags[0]
    assert "Counter.hits" in d.message
    assert "thread:_loop" in d.message and "external" in d.message


def test_guarded_and_sentinel_writes_are_clean():
    """``total`` (every write under _lock) and ``_running`` (atomic
    sentinel stores) must not be findings."""
    diags = sharedstate.run(fixture_ctx("fx_shared_state.py"))
    for d in diags:
        assert "Counter.total" not in d.message
        assert "_running" not in d.message


# ---------------------------------------------------------------------------
# pass 8: thread-life
# ---------------------------------------------------------------------------

def test_thread_life_daemon_and_drain_detected():
    diags = threadlife.run(fixture_ctx("fx_thread_life.py"))
    assert codes(diags) == {"daemon-unset", "undrained-thread"}
    # both findings anchor Leaky.start's construction site
    src = (FIXTURES / "fx_thread_life.py").read_text().splitlines()
    ctor_line = next(i for i, t in enumerate(src, 1)
                     if "threading.Thread(target=self._run)" in t)
    assert {d.line for d in diags} == {ctor_line}


def test_thread_life_swap_join_and_teardown_are_clean():
    """Disciplined: daemon explicit + the swap-join drain
    (``t, self._t = self._t, None; t.join()``) and a teardown-helper
    thread (target named ``*_shutdown``) — no findings."""
    diags = threadlife.run(fixture_ctx("fx_thread_life.py"))
    assert all("Disciplined" not in d.message and "_ticker" not in
               d.message for d in diags)


# ---------------------------------------------------------------------------
# block_line anchoring under nested with statements
# ---------------------------------------------------------------------------

def test_nested_with_anchors_innermost_lock():
    diags = lockorder.run(fixture_ctx("fx_nested_with.py"))
    by_msg = {d.message: d for d in diags}
    inner = next(d for d in diags if "_inner" in d.message)
    outer = next(d for d in diags if "_outer" in d.message)
    assert inner.block_line == inner.line - 1   # the inner with
    assert outer.block_line == outer.line - 1
    assert inner.block_line != outer.block_line


def test_outer_block_entry_does_not_cover_inner_lock():
    """An allowlist ``block = true`` entry anchored on the OUTER with
    must not suppress a finding under the distinct INNER lock (the v1
    anchoring bug this release fixes)."""
    ctx = fixture_ctx("fx_nested_with.py")
    diags = lockorder.run(ctx)
    inner = next(d for d in diags if "_inner" in d.message)
    outer_with = inner.block_line - 1           # `with self._outer:`
    allow = Allowlist([{
        "pass": "lock-order", "file": "fx_nested_with.py",
        "line": outer_with, "block": True,
        "contains": "with self._outer:", "reason": "outer only"}])
    allow.apply(diags, ctx)
    assert not inner.allowed, \
        "outer block entry silently covered the inner-lock finding"
    # and covering the inner lock requires anchoring ITS with
    diags2 = lockorder.run(ctx)
    inner2 = next(d for d in diags2 if "_inner" in d.message)
    allow2 = Allowlist([{
        "pass": "lock-order", "file": "fx_nested_with.py",
        "line": inner2.block_line, "block": True,
        "contains": "with self._inner:", "reason": "inner hold"}])
    allow2.apply(diags2, ctx)
    assert inner2.allowed


# ---------------------------------------------------------------------------

def test_unused_allowlist_entry_is_an_error():
    ctx = fixture_ctx("fx_lock_cycle.py")
    allow = Allowlist([{
        "pass": "lock-order", "file": "fx_lock_cycle.py", "line": 10,
        "contains": "with A_LOCK:", "reason": "suppresses nothing"}])
    diags = lockorder.run(ctx)
    allow.apply(diags, ctx)
    assert any("unused allowlist entry" in e for e in allow.errors)


def test_stale_allowlist_anchor_is_an_error():
    ctx = fixture_ctx("fx_blocked_hold.py")
    allow = Allowlist([{
        "pass": "lock-order", "file": "fx_blocked_hold.py", "line": 13,
        "contains": "code that is not on this line", "reason": "stale"}])
    allow.apply(lockorder.run(ctx), ctx)
    assert any("stale allowlist entry" in e for e in allow.errors)


def test_allowlist_entry_requires_reason():
    allow = Allowlist([{"pass": "lock-order", "file": "x.py", "line": 1,
                        "contains": "x"}])  # no reason
    assert any("rationale" in e for e in allow.errors)


def test_mini_toml_parses_allow_entries():
    data = parse_mini_toml(
        '# comment\n[[allow]]\npass = "lock-order"\nline = 42\n'
        'block = true\nreason = "why \\"quoted\\""\n[[allow]]\n'
        'file = "a.py"  # trailing comment\n')
    assert len(data["allow"]) == 2
    assert data["allow"][0]["line"] == 42
    assert data["allow"][0]["block"] is True
    assert data["allow"][0]["reason"] == 'why "quoted"'
    assert data["allow"][1]["file"] == "a.py"


def test_repo_allowlist_parses_and_every_entry_has_reason():
    allow = Allowlist.load()
    assert allow.entries, "repo allowlist should not be empty"
    assert allow.errors == []
    assert all(e.get("reason") for e in allow.entries)


# ---------------------------------------------------------------------------
# the real tree (the CI gate)
# ---------------------------------------------------------------------------

def test_real_tree_is_green():
    """`python -m tools.analysis` on this checkout: zero un-allowlisted
    findings, zero allowlist errors — the blocking-lane condition."""
    diags, errors = run_all()
    active = [d for d in diags if not d.allowed]
    assert active == [], "\n".join(d.format() for d in active)
    assert errors == [], "\n".join(errors)
    # and the allowlist is actually exercised (no vacuous green)
    assert any(d.allowed for d in diags)


def test_real_tree_knob_parity_proves_the_fixed_drifts():
    """The four ISSUE-5 drifts stay fixed: the three code-side knobs are
    documented, and no doc token lacks a code read."""
    ctx = AnalysisContext.for_repo()
    diags = knobs.run(ctx)
    assert diags == [], "\n".join(d.format() for d in diags)
    collected = knobs.collect_knobs(ctx)
    documented = knobs.doc_knob_tokens(ctx)
    for name in ("SONATA_ESPEAKNG_DATA_DIRECTORY", "SONATA_TCONV"):
        assert name in documented, f"{name} row lost from the docs"
        assert collected[name].reads, f"{name} no longer read in code"
    assert "SONATA_PROFILE" not in documented  # re-wired to /debug/profile


def test_cli_json_format(capsys):
    from tools.analysis.__main__ import main

    rc = main(["--format", "json"])
    out = capsys.readouterr().out
    report = json.loads(out)
    assert rc == 0
    assert report["ok"] is True
    assert report["findings"] == []
    assert report["allowlisted"], "allowlist should be exercised"
    assert {f["pass"] for f in report["allowlisted"]} <= {
        p.PASS_NAME for p in PASSES}


def test_cli_partial_pass_run_is_green(capsys):
    """--pass <name> must not report other passes' allowlist entries as
    unused (review-pass fix): a partial run on the green tree exits 0."""
    from tools.analysis.__main__ import main

    for pass_name in ("knobs", "lock-order"):
        rc = main(["--pass", pass_name, "--format", "json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0, report["allowlist_errors"]
        assert report["allowlist_errors"] == []


def test_cli_report_flag_writes_artifact(tmp_path, capsys):
    """--report writes the JSON artifact from the SAME analysis run that
    feeds the log (review-pass fix: no second run, no `|| true`)."""
    from tools.analysis.__main__ import main

    out = tmp_path / "report.json"
    rc = main(["--report", str(out)])
    capsys.readouterr()
    report = json.loads(out.read_text())
    assert rc == 0
    assert report["ok"] is True and report["findings"] == []


def test_render_report_text_counts():
    diags, errors = run_all()
    text = render_report(diags, errors, "text")
    assert "sonata-lint:" in text.splitlines()[-1]
    assert "0 finding(s)" in text.splitlines()[-1]


def test_allowlist_entry_count_does_not_grow():
    """The v2 re-audit contract (ROADMAP trajectory goal): deepening
    the analyzer must not be bought with suppressions.  9 entries was
    the pre-v2 count; new passes and the rename revert landed without
    adding one.  Lowering this bound is progress; raising it needs the
    same scrutiny as a production lock."""
    assert len(Allowlist.load().entries) <= 9


def test_new_passes_registered():
    names = {p.PASS_NAME for p in PASSES}
    assert {"yield-lock", "shared-state", "thread-life"} <= names


def test_committed_report_matches_fresh_run():
    """tools/analysis_report.json must equal a fresh run — the same
    freshness assertion the CI lane makes, so a code change that moves
    any finding (or allowlisted line) cannot land without regenerating
    the artifact in the same commit."""
    diags, errors = run_all()
    fresh = render_report(diags, errors, "json") + "\n"
    committed = (REPO / "tools" / "analysis_report.json").read_text(
        encoding="utf-8")
    assert fresh == committed, \
        "stale tools/analysis_report.json — re-run " \
        "`python -m tools.analysis --report tools/analysis_report.json`"


def test_cli_timing_prints_per_pass_and_respects_budget(capsys):
    from tools.analysis.__main__ import main, TIMING_BUDGET_S

    rc = main(["--timing"])
    out = capsys.readouterr().out
    assert rc == 0, "timing run failed (findings or budget)"
    timing_lines = [ln for ln in out.splitlines()
                    if ln.startswith("timing:")]
    reported = {ln.split()[1] for ln in timing_lines}
    assert {p.PASS_NAME for p in PASSES} <= reported
    total_line = next(ln for ln in timing_lines if " total " in ln)
    assert f"budget {TIMING_BUDGET_S:g}s" in total_line
