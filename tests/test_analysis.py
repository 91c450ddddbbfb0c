"""mtlint analyzer tests: seeded-violation fixtures must be detected by
the right rule at the right location, the clean fixture must be silent,
and — the tier-1 gate — the real tree must carry zero unsuppressed
findings under the checked-in mtlint.toml baseline.
"""

import pathlib
import subprocess
import sys

import pytest

from mpit_tpu.analysis import load_config, run
from mpit_tpu.analysis.config import ConfigError, parse_toml_subset

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "mtlint"
BADPKG = FIXTURES / "badpkg"
CLEANPKG = FIXTURES / "cleanpkg"


def _findings(target, config=None):
    return run(target, config).findings


def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


# -- seeded violations (the four the acceptance criteria name, plus the
# rest of the rule catalog) -------------------------------------------------


class TestSeededViolations:
    @pytest.fixture(scope="class")
    def bad(self):
        return _by_rule(_findings(BADPKG))

    def test_tag_mismatch_detected(self, bad):
        # Seed 1: client sends PING, server never receives it.
        hits = [f for f in bad.get("MT-P102", []) if "PING" in f.message]
        assert len(hits) == 1
        assert hits[0].path == "client.py"
        assert hits[0].line == 9

    def test_missing_ack_write_path_detected(self, bad):
        # Seed 2: push_grad ships GRAD without awaiting GRAD_ACK; seed 2b:
        # _post_push is a helper whose naked PARAM_PUSH send no caller
        # vouches for (the interprocedural scan must not excuse it).
        hits = sorted(bad.get("MT-P103", []), key=lambda f: f.line)
        assert len(hits) == 2
        assert (hits[0].path, hits[0].line) == ("client.py", 15)
        assert "GRAD" in hits[0].message and "GRAD_ACK" in hits[0].message
        assert (hits[1].path, hits[1].line) == ("client.py", 37)
        assert "PARAM_PUSH" in hits[1].message

    def test_helper_split_acks_are_followed(self, bad):
        # The §12/§13 helper-split shapes (cleanpkg stream_grads /
        # serve_grad_chunks / badpkg absorb_push) must be SILENT: the
        # scan follows one level of helper calls in both directions,
        # resolving parameter-carried tags at the call site.
        assert not [f for f in bad.get("MT-P103", [])
                    if "absorb_push" in f.message
                    or "_ack_push" in f.message]

    def test_lock_order_inversion_detected(self, bad):
        # Seed 3: a_then_b takes _lock->_cv, b_then_a takes _cv->_lock.
        hits = bad.get("MT-C201", [])
        assert {(f.path, f.line) for f in hits} == {
            ("locks.py", 17), ("locks.py", 22)}

    def test_host_sync_in_jit_detected(self, bad):
        # Seed 4: float() on a traced value inside the jitted bad_step.
        hits = [f for f in bad.get("MT-J301", []) if "float()" in f.message]
        assert len(hits) == 1
        assert (hits[0].path, hits[0].line) == ("hotpath.py", 9)

    def test_unused_tag_detected(self, bad):
        hits = bad.get("MT-P101", [])
        assert [(f.path, f.line) for f in hits] == [("tags.py", 8)]
        assert "ORPHAN" in hits[0].message

    def test_recv_recv_deadlock_detected(self, bad):
        locs = {(f.path, f.line) for f in bad.get("MT-P104", [])}
        assert ("client.py", 21) in locs  # fetch: recv REPLY before send REQ

    def test_blocking_under_lock_detected(self, bad):
        locs = {(f.path, f.line) for f in bad.get("MT-C202", [])}
        assert ("locks.py", 27) in locs

    def test_unbounded_aio_detected(self, bad):
        # MT-P201: every badpkg aio call lacks deadline=/abort=.
        locs = {(f.path, f.line) for f in bad.get("MT-P201", [])}
        assert ("client.py", 9) in locs
        assert ("server.py", 16) in locs

    def test_blocking_convenience_detected(self, bad):
        # MT-P202: the seeded transport.recv() busy-wait in drain().
        hits = bad.get("MT-P202", [])
        assert [(f.path, f.line) for f in hits] == [("server.py", 22)]

    def test_event_loop_blocking_detected(self, bad):
        # MT-P203: raw recv + time.sleep + sendall inside _el_* callbacks
        # (tcp.py fixture) PLUS the interprocedural seed: a raw recv one
        # helper below _el_on_timer, flagged at the blocking site inside
        # the helper.  The cleanpkg _nb_*-helper shapes (one and two
        # levels deep) must be silent (test_clean_fixture_is_silent).
        hits = bad.get("MT-P203", [])
        assert {(f.path, f.line) for f in hits} == {
            ("tcp.py", 9), ("tcp.py", 11), ("tcp.py", 16), ("tcp.py", 21)}
        assert all("event-loop callback" in f.message for f in hits)

    def test_event_loop_blocking_through_helper_names_the_path(self, bad):
        # The interprocedural finding must name both the helper that
        # blocks and the callback that reaches it — exactly once.
        hits = [f for f in bad.get("MT-P203", []) if f.line == 21]
        assert len(hits) == 1
        assert "_pump_once" in hits[0].message
        assert "_el_on_timer" in hits[0].message

    def test_interprocedural_blocking_under_lock_detected(self, bad):
        # MT-C202 via the call graph: hold_and_flush blocks one helper
        # down (slow_flush -> time.sleep) — exactly one finding, at the
        # call site under the lock.
        hits = [f for f in bad.get("MT-C202", [])
                if (f.path, f.line) == ("locks.py", 47)]
        assert len(hits) == 1
        assert "slow_flush" in hits[0].message

    def test_lock_across_scheduler_yield_detected(self, bad):
        # MT-Y803: hold_and_greet holds _lock across nap_via_sched(),
        # which re-enters the scheduler — exactly one finding.
        hits = bad.get("MT-Y803", [])
        assert [(f.path, f.line) for f in hits] == [("locks.py", 40)]
        assert "nap_via_sched" in hits[0].message

    def test_atomic_section_yield_detected(self, bad):
        # MT-Y801: a yield inside the declared read window of the
        # fixture ps/server.py — exactly one finding.
        hits = bad.get("MT-Y801", [])
        assert [(f.path, f.line) for f in hits] == [("ps/server.py", 21)]
        assert "ps-read-snapshot-window" in hits[0].message

    def test_single_writer_escape_detected(self, bad):
        # MT-Y802: steal_ticket pops the device plane outside the
        # declared writer set — exactly one finding.  The cleanpkg twin
        # pops one helper BELOW the declared writer and must stay
        # silent (test_clean_fixture_is_silent).
        hits = bad.get("MT-Y802", [])
        assert [(f.path, f.line) for f in hits] == [("ps/server.py", 26)]
        assert "dplane-single-writer" in hits[0].message

    def test_unowned_buffer_at_seam_detected(self, bad):
        # MT-D901: a frombuffer view reaches the donated chunk apply,
        # plus the two pool-seam seeds (server scatter, client decode)
        # — one finding each, nothing else.
        hits = bad.get("MT-D901", [])
        assert {(f.path, f.line) for f in hits} == {
            ("ps/server.py", 31), ("ps/server.py", 47),
            ("ps/client.py", 12)}
        assert all("frombuffer" in f.message for f in hits)

    def test_ownership_wrapper_dropped_detected(self, bad):
        # MT-D903, both shapes: an unprovable sink argument
        # (ps/server.py) and a declared owned path whose device_copy
        # wrapper is gone (dplane/hbm.py) — plus the pool-seam
        # owned-copy paths: a stray np.array outside the submit
        # boundary on both the client decode and server scatter sides.
        hits = bad.get("MT-D903", [])
        assert {(f.path, f.line) for f in hits} == {
            ("ps/server.py", 36), ("dplane/hbm.py", 14),
            ("ps/client.py", 16), ("ps/server.py", 51)}

    def test_donated_slot_leak_detected(self, bad):
        # MT-D902: snapshot_host caches the bare donated buffer —
        # exactly one finding.
        hits = bad.get("MT-D902", [])
        assert [(f.path, f.line) for f in hits] == [("dplane/hbm.py", 19)]
        assert "self.param" in hits[0].message

    def test_signal_handler_blocking_detected(self, bad):
        # MT-P204: every call in the seeded SIGTERM handler (lock,
        # allocation, transport send, sleep) is a finding; the cleanpkg
        # flags-and-pipe handler must stay silent (asserted by
        # test_clean_fixture_is_silent).
        hits = bad.get("MT-P204", [])
        assert {(f.path, f.line) for f in hits} == {
            ("preempt.py", 18), ("preempt.py", 19),
            ("preempt.py", 20), ("preempt.py", 21)}
        assert all("SIGTERM handler" in f.message for f in hits)

    def test_yield_under_lock_detected(self, bad):
        hits = bad.get("MT-C203", [])
        assert [(f.path, f.line) for f in hits] == [("locks.py", 31)]

    def test_pool_wait_under_lock_detected(self, bad):
        # MT-C204 lock half: hold_and_collect blocks on a pool job with
        # _lock held (direct), hold_and_drain one helper down — one
        # finding each, at the call site under the lock.
        hits = sorted((f for f in bad.get("MT-C204", [])
                       if f.path == "pool.py"), key=lambda f: f.line)
        assert [(f.path, f.line) for f in hits] == [
            ("pool.py", 14), ("pool.py", 21)]
        assert "result" in hits[0].message
        assert "_drain_job" in hits[1].message

    def test_pool_wait_in_atomic_window_detected(self, bad):
        # MT-C204 window half: a Job.result() inside the declared
        # yield-free read-path window — exactly one finding, naming
        # the section.  The cleanpkg done()-under-lock and
        # join-outside-mutex twins must be silent
        # (test_clean_fixture_is_silent).
        hits = sorted((f for f in bad.get("MT-C204", [])
                       if f.path == "ps/server.py"), key=lambda f: f.line)
        # the helper's own wait, and the read window that calls it
        assert [(f.path, f.line) for f in hits] == [
            ("ps/server.py", 19), ("ps/server.py", 41)]
        assert "ps-read-snapshot-window" in hits[0].message
        assert "ps-read-path-helpers" in hits[1].message

    def test_traced_branch_detected(self, bad):
        hits = bad.get("MT-J302", [])
        assert [(f.path, f.line) for f in hits] == [("hotpath.py", 10)]

    def test_missing_donate_detected(self, bad):
        locs = {(f.path, f.line) for f in bad.get("MT-J303", [])}
        assert ("hotpath.py", 19) in locs

    def test_raw_timing_detected(self, bad):
        # MT-O401: the seeded wall-clock read and the monotonic elapsed
        # subtraction in timing_report — deadline arithmetic elsewhere in
        # the fixtures (additions/comparisons) must not fire.
        locs = {(f.path, f.line) for f in bad.get("MT-O401", [])}
        assert locs == {("server.py", 28), ("server.py", 31)}

    def test_print_reporting_detected(self, bad):
        hits = bad.get("MT-O402", [])
        assert [(f.path, f.line) for f in hits] == [("server.py", 32)]
        assert "registry snapshot" in hits[0].message

    def test_unregistered_tag_detected(self, bad):
        # MT-P501: ROGUE is used by both roles (so MT-P101/P102 stay
        # quiet) but has no TAG_PAIRS entry.
        hits = bad.get("MT-P501", [])
        assert [(f.path, f.line) for f in hits] == [("tags.py", 9)]
        assert "ROGUE" in hits[0].message and "TAG_PAIRS" in hits[0].message

    def test_undocumented_tag_detected(self, bad):
        # MT-P502: ROGUE is absent from the fixture's docs/PROTOCOL.md.
        hits = bad.get("MT-P502", [])
        assert [(f.path, f.line) for f in hits] == [("tags.py", 9)]
        assert "PROTOCOL.md" in hits[0].message

    def test_undocumented_metric_detected(self, bad):
        # MT-O403: mpit_rogue_widgets_total is instantiated but absent
        # from the fixture's docs/OBSERVABILITY.md; the documented
        # mpit_good_widgets_total on the line above stays silent.
        hits = bad.get("MT-O403", [])
        assert [(f.path, f.line) for f in hits] == [("server.py", 46)]
        assert "mpit_rogue_widgets_total" in hits[0].message
        assert "OBSERVABILITY.md" in hits[0].message

    def test_undocumented_phase_detected(self, bad):
        # MT-O404: rogue_phase is marked but absent from the fixture's
        # docs/OBSERVABILITY.md phase taxonomy; the documented
        # good_phase on the line above stays silent.
        hits = bad.get("MT-O404", [])
        assert [(f.path, f.line) for f in hits] == [("server.py", 54)]
        assert "rogue_phase" in hits[0].message
        assert "OBSERVABILITY.md" in hits[0].message

    def test_dplane_host_transfer_detected(self, bad):
        # Seeds: np.asarray in apply_update, .item() + device_get in
        # sync_round — and nothing from the name-exempted
        # snapshot_host/timing_probe bodies.
        hits = bad.get("MT-J311", [])
        assert {(f.path, f.line) for f in hits} == {
            ("dplane/exchange.py", 10),
            ("dplane/exchange.py", 21),
            ("dplane/exchange.py", 22)}

    def test_dplane_device_barrier_detected(self, bad):
        hits = bad.get("MT-J312", [])
        assert [(f.path, f.line) for f in hits] == [
            ("dplane/exchange.py", 16)]
        assert "block_until_ready" in hits[0].message

    def test_nonbinary_pairs_exempt_from_role_model(self, bad):
        # The pairing table is what exempts controller / server<->server
        # tags from MT-P101/P102 — the badpkg table is all-binary, so
        # its seeded P101/P102 findings must be unaffected (asserted
        # elsewhere); here: the real tree's shardctl tags lean on it.
        from mpit_tpu.analysis.protocol import _binary_pair

        assert _binary_pair(None) is True
        assert _binary_pair(("client", "server")) is True
        assert _binary_pair(("server", "client")) is True
        assert _binary_pair(("server", "server")) is False
        assert _binary_pair(("controller|server", "server|client")) is False


def test_clean_fixture_is_silent():
    assert _findings(CLEANPKG) == []


# -- baseline / config ------------------------------------------------------


def test_repo_baseline_loads_and_every_entry_is_justified():
    cfg = load_config(REPO / "mtlint.toml")
    assert cfg.suppressions, "baseline exists but parsed empty"
    for s in cfg.suppressions:
        assert s.reason.strip(), f"unjustified baseline entry: {s.rule} @ {s.file}"


def test_baseline_rejects_entries_without_reason(tmp_path):
    bad = tmp_path / "mtlint.toml"
    bad.write_text('[[suppress]]\nrule = "MT-C202"\nfile = "x.py"\n')
    with pytest.raises(ConfigError, match="reason"):
        load_config(bad)


def test_toml_subset_parser_roundtrip():
    data = parse_toml_subset(
        '# comment\n[[suppress]]\nrule = "MT-X" # trailing\nline = 3\n'
        '[[suppress]]\nrule = "MT-Y"\nflags = ["a", "b"]\nok = true\n')
    assert data["suppress"][0] == {"rule": "MT-X", "line": 3}
    assert data["suppress"][1] == {"rule": "MT-Y", "flags": ["a", "b"],
                                   "ok": True}


def test_suppression_matching_and_unused_accounting():
    cfg = load_config(REPO / "mtlint.toml")
    report = run(REPO / "mpit_tpu", cfg)
    # Every baseline entry must still match a live finding — a stale
    # entry means the finding was fixed and the entry must be removed.
    assert report.unused_suppressions == [], [
        s.render() for s in report.unused_suppressions]


# -- the tier-1 gate --------------------------------------------------------


def test_real_tree_has_zero_unsuppressed_findings():
    cfg = load_config(REPO / "mtlint.toml")
    report = run(REPO / "mpit_tpu", cfg)
    assert report.findings == [], "\n" + "\n".join(
        f.render() for f in report.findings)


def test_cli_exit_codes():
    env_root = str(REPO)
    ok = subprocess.run(
        [sys.executable, "tools/mtlint.py", "mpit_tpu", "--quiet"],
        cwd=env_root, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, "tools/mtlint.py",
         "tests/fixtures/mtlint/badpkg", "--quiet"],
        cwd=env_root, capture_output=True, text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "MT-P103" in bad.stdout  # findings reach the console


# -- wire-schema conformance (MT-S6xx) --------------------------------------


class TestSchemaConformance:
    DRIFTPKG = FIXTURES / "driftpkg"

    @pytest.fixture(scope="class")
    def drift(self):
        return _by_rule(_findings(self.DRIFTPKG))

    def test_live_tree_is_conformant(self):
        from mpit_tpu.analysis import schema
        from mpit_tpu.analysis.core import collect

        files, errs = collect(REPO / "mpit_tpu")
        assert errs == []
        assert schema.check(files) == []

    def test_constant_drift_detected(self, drift):
        hits = drift.get("MT-S601", [])
        locs = {(f.path, f.line) for f in hits}
        assert ("ft/wire.py", 7) in locs  # HDR_BYTES = 24 vs schema 16
        assert any("HDR_BYTES" in f.message and "16" in f.message
                   for f in hits)
        # FLAG_ROGUE: a constant the registry does not declare
        assert any("FLAG_ROGUE" in f.message for f in hits)

    def test_struct_width_drift_detected(self, drift):
        hits = drift.get("MT-S602", [])
        # init_v3 grew to six words; rogue_frame is registered nowhere
        assert any("init_v3" in f.message and "6-word" in f.message
                   for f in hits)
        assert any("rogue_frame" in f.message for f in hits)

    def test_tag_registry_drift_detected(self, drift):
        msgs = [f.message for f in drift.get("MT-S603", [])]
        assert any("REDUCE = 18" in m for m in msgs)
        assert any("SIDEBAND" in m for m in msgs)
        assert any("TAG_PAIRS['REDUCE_ACK']" in m for m in msgs)
        # a tree that still assigns the retired ids has drifted
        assert any("tag DIFF = 14 is not in the schema" in m for m in msgs)
        assert any("tag DIFF_REQ = 15 is not in the" in m for m in msgs)
        assert any("TAG_PAIRS row 'DIFF' names a tag" in m for m in msgs)

    def test_retired_surface_is_assigned_to_nothing(self):
        # Tags 14 and 15 and bit 5 of the v3/v5 flags word went with the
        # multi-cell fabric (PROTOCOL.md §11) and are not reused: no row
        # in the registry, no constant in the modules it describes.
        import ast

        from mpit_tpu.analysis import schema

        assert not {t.id for t in schema.TAGS} & {14, 15}
        assert len(schema.TAGS) == 15
        assert sorted(f.bit for f in schema.V3_FLAGS) == [
            1, 2, 4, 8, 16, 64]
        assert schema.RETIRED_V3_BITS == 32

        def consts(rel):
            tree = ast.parse((REPO / "mpit_tpu" / rel).read_text())
            return {k: v for k, (v, _) in
                    schema._module_consts(tree).items()}

        assert not set(consts("ps/tags.py").values()) & {14, 15}
        flags = {k: v for k, v in consts("ft/wire.py").items()
                 if k.startswith("FLAG_")}
        assert flags == {"FLAG_FRAMED": 1, "FLAG_HEARTBEAT": 2,
                         "FLAG_STALENESS": 4, "FLAG_TIMING": 8,
                         "FLAG_READONLY": 16, "FLAG_CHUNKED": 64}

    def test_clean_fixture_has_no_schema_findings(self):
        by = _by_rule(_findings(CLEANPKG))
        assert not any(r.startswith("MT-S6") for r in by)

    def test_negotiation_lattice_extraction_matches_schema(self):
        # The live _negotiate enforces exactly the declared REFUSALS —
        # asserted through the engine: zero MT-S604/S605 on the tree
        # (covered by test_live_tree_is_conformant) AND a doctored
        # guard is caught.
        import textwrap

        from mpit_tpu.analysis import schema
        from mpit_tpu.analysis.core import collect

        src = (REPO / "mpit_tpu" / "ps" / "server.py").read_text()
        # Drop the READONLY-requires-FRAMED guard: conformance must
        # notice the declared rule is no longer enforced.
        doctored = src.replace(
            "if ro and not (flags & FLAG_FRAMED):", "if False:")
        assert doctored != src
        import pathlib
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            p = pathlib.Path(td) / "ps" / "server.py"
            p.parent.mkdir()
            p.write_text(doctored)
            files, _ = collect(pathlib.Path(td))
            findings = schema.check(files)
        assert any(f.rule == "MT-S605" and "READONLY" in f.message
                   and "FRAMED" in f.message for f in findings), [
            f.render() for f in findings]


class TestSchemaDocs:
    def test_emit_docs_check_clean_on_tree(self):
        r = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "schema",
             "--emit-docs", "--check", "--root", str(REPO)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_check_nonzero_on_drift_fixture(self):
        r = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "schema",
             "--check", "--root",
             str(FIXTURES / "driftpkg")],
            capture_output=True, text=True)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "MT-S601" in r.stdout and "MT-S603" in r.stdout
        assert "doc drift" in r.stdout

    def test_generated_markers_present_in_protocol_md(self):
        doc = (REPO / "docs" / "PROTOCOL.md").read_text()
        for name in ("tag-table", "init-table", "flag-table"):
            assert f"BEGIN GENERATED: mtlint-schema {name}" in doc
            assert f"END GENERATED: mtlint-schema {name}" in doc

    def test_doc_drift_detected_after_hand_edit(self, tmp_path):
        from mpit_tpu.analysis import schema

        root = tmp_path / "docs"
        root.mkdir()
        doc = root / "PROTOCOL.md"
        src = (REPO / "docs" / "PROTOCOL.md").read_text()
        doc.write_text(src.replace("| `GRAD` (2) |", "| `GRAD` (99) |"))
        drift = schema.emit_docs(doc, check=True)
        assert any("tag-table" in d for d in drift)
        # and the clean copy is quiet
        doc.write_text(src)
        assert schema.emit_docs(doc, check=True) == []

    def test_oracle_agrees_with_declared_lattice(self):
        from mpit_tpu.analysis import schema

        # requires edges refuse
        for version, bit, missing in ((3, "READONLY", "FRAMED"),
                                      (5, "CHUNKED", "FRAMED")):
            out = schema.negotiate(version, schema.flag_bits(bit),
                                   reader_rank=True)
            assert not out.accepted and missing in out.reason
        # negotiate-off is silent, not a refusal
        out = schema.negotiate(3, schema.flag_bits("STALENESS"))
        assert out.accepted and not out.staleness
        out = schema.negotiate(
            3, schema.flag_bits("FRAMED", "STALENESS", "TIMING"))
        assert out.accepted and out.staleness and out.timing


# -- bounded interleaving model checker (MT-M7xx) ---------------------------


class TestModelCheck:
    MACHINES = FIXTURES / "machines"

    def test_live_handshakes_explore_clean(self):
        from mpit_tpu.analysis import modelcheck

        results = modelcheck.check_all()
        assert {r.machine for r in results} == {
            "init-grad-stop", "param-read", "retire", "preempt"}
        for r in results:
            assert r.clean, [v.render() for v in r.violations]
            assert r.states_fault_free > 0
            assert not r.truncated

    @pytest.mark.parametrize("fixture,rule", [
        ("deadlock.py", "MT-M701"),
        ("unreachable_ack.py", "MT-M702"),
        ("unacked_terminal.py", "MT-M703"),
    ])
    def test_seeded_fixture_fires(self, fixture, rule):
        from mpit_tpu.analysis import modelcheck

        machines = modelcheck.load_machines_file(self.MACHINES / fixture)
        results = modelcheck.check_all(machines)
        rules = {v.rule for r in results for v in r.violations}
        assert rule in rules, (fixture, rules)

    def test_deadlock_trace_names_both_blocked_recvs(self):
        from mpit_tpu.analysis import modelcheck

        machines = modelcheck.load_machines_file(
            self.MACHINES / "deadlock.py")
        (res,) = modelcheck.check_all(machines)
        (v,) = [v for v in res.violations if v.rule == "MT-M701"]
        assert "blocked on recv(REPLY)" in v.detail
        assert "blocked on recv(REQ)" in v.detail

    def test_cli_exit_codes_and_report(self, tmp_path):
        report = tmp_path / "mc.json"
        ok = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "modelcheck",
             "--report", str(report)],
            cwd=str(REPO), capture_output=True, text=True)
        assert ok.returncode == 0, ok.stdout + ok.stderr
        import json

        data = json.loads(report.read_text())
        assert data["schema"] == "mpit_modelcheck/1"
        assert data["clean"] is True
        assert len(data["machines"]) == 4
        assert data["total_states"] > 0
        bad = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "modelcheck",
             "--machines",
             str(self.MACHINES / "deadlock.py")],
            cwd=str(REPO), capture_output=True, text=True)
        assert bad.returncode == 1
        assert "MT-M701" in bad.stdout

    def test_dup_toggle_widens_the_state_space(self):
        from mpit_tpu.analysis import modelcheck

        m = {r.machine: r for r in modelcheck.check_all()}
        r = m["init-grad-stop"]
        assert r.states_faulty > r.states_fault_free


# -- declared concurrency/ownership disciplines (MT-Y8xx / MT-D9xx) ---------


class TestDisciplines:
    def test_real_tree_disciplines_all_verified(self):
        # The acceptance gate: every declared discipline matches live
        # code sites (no stale declarations) and verifies clean.
        from mpit_tpu.analysis import disciplines

        rep = disciplines.coverage_report(REPO / "mpit_tpu")
        assert rep["schema"] == "mpit_disciplines/1"
        assert rep["stale"] == 0, [
            r["name"] for r in rep["disciplines"] if r["status"] == "stale"]
        assert rep["violated"] == 0, [
            r for r in rep["disciplines"] if r["status"] == "violated"]
        assert rep["verified"] >= 6
        # The minimum coverage the spec names: the §8 read window,
        # one single-writer per plane, and the donation seam.
        names = {r["name"] for r in rep["disciplines"]}
        assert {"ps-read-snapshot-window", "dplane-single-writer",
                "aggplane-single-writer", "reader-single-writer",
                "chunk-apply-owned-seam",
                "pool-client-decode-owned",
                "pool-server-scatter-owned"} <= names

    def test_cli_report_and_exit_codes(self, tmp_path):
        report = tmp_path / "disc.json"
        ok = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "disciplines",
             "--report", str(report)],
            cwd=str(REPO), capture_output=True, text=True)
        assert ok.returncode == 0, ok.stdout + ok.stderr
        import json

        data = json.loads(report.read_text())
        assert data["schema"] == "mpit_disciplines/1"
        assert data["verified"] >= 6 and data["stale"] == 0
        assert all(r["status"] == "verified" for r in data["disciplines"])

    def test_stale_declaration_gate(self, tmp_path):
        # A tree with none of the declared files: every row is stale and
        # the CLI fails — a registry that matches nothing is drift, the
        # same spirit as a stale baseline entry.
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "other.py").write_text("def f():\n    return 1\n")
        r = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "disciplines",
             "--root", str(pkg)],
            cwd=str(REPO), capture_output=True, text=True)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "stale" in r.stdout

    # -- mutation proofs: breaking a guarded site turns the tree red --------

    def _doctored(self, tmp_path, rel, old, new):
        import pathlib as _p

        src = (REPO / "mpit_tpu" / rel).read_text()
        assert old in src
        doctored = src.replace(old, new)
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(doctored)
        from mpit_tpu.analysis.core import collect

        files, errs = collect(_p.Path(tmp_path))
        assert errs == []
        return files

    def test_yield_in_read_window_turns_tree_red(self, tmp_path):
        from mpit_tpu.analysis import disciplines

        files = self._doctored(
            tmp_path, "ps/server.py",
            "wire = self._snapshot_wire(codec)\n        header =",
            "wire = self._snapshot_wire(codec)\n        yield None\n"
            "        header =")
        findings = disciplines.check(files)
        assert any(f.rule == "MT-Y801" for f in findings), [
            f.render() for f in findings]

    def test_bypassing_chunk_owned_turns_tree_red(self, tmp_path):
        from mpit_tpu.analysis import ownership

        files = self._doctored(
            tmp_path, "ps/server.py",
            "self._chunk_owned(body.view(self.dtype))",
            "body.view(self.dtype)")
        findings = ownership.check(files)
        assert any(f.rule in ("MT-D901", "MT-D903") for f in findings), [
            f.render() for f in findings]

    def test_dropping_device_copy_on_seed_turns_tree_red(self, tmp_path):
        from mpit_tpu.analysis import ownership

        files = self._doctored(
            tmp_path, "dplane/hbm.py",
            "self.param = device_copy(place_flat(value, self.config))",
            "self.param = place_flat(value, self.config)")
        findings = ownership.check(files)
        assert any(f.rule == "MT-D903" for f in findings), [
            f.render() for f in findings]

    def test_dropping_decode_snapshot_turns_tree_red(self, tmp_path):
        # The pool seam's ownership pin: submitting the reused rx frame
        # to a pooled decode without the np.array snapshot must flag.
        from mpit_tpu.analysis import ownership

        files = self._doctored(
            tmp_path, "ps/client.py",
            "self.codec, np.array(body), out[lo:hi])",
            "self.codec, body, out[lo:hi])")
        findings = ownership.check(files)
        assert any(f.rule in ("MT-D901", "MT-D903") for f in findings), [
            f.render() for f in findings]

    def test_pool_wait_in_real_window_turns_tree_red(self, tmp_path):
        # MT-C204's window half against the real tree: a blocking
        # Job.result() planted inside _snapshot_wire (a declared
        # yield-free read-path helper) must flag.
        from mpit_tpu.analysis import callgraph, concurrency

        files = self._doctored(
            tmp_path, "ps/server.py",
            'def _snapshot_wire(self, codec: "codec_mod.Codec") '
            "-> np.ndarray:",
            'def _snapshot_wire(self, codec: "codec_mod.Codec") '
            "-> np.ndarray:\n        self.job.result()")
        graph = callgraph.build_graph(files)
        findings = concurrency.check(files, graph)
        assert any(f.rule == "MT-C204" for f in findings), [
            f.render() for f in findings]

    def test_caching_bare_snapshot_turns_tree_red(self, tmp_path):
        from mpit_tpu.analysis import ownership

        files = self._doctored(
            tmp_path, "dplane/hbm.py",
            "self._snap_host = (self.version, np.asarray(self.param))",
            "self._snap_host = (self.version, self.param)")
        findings = ownership.check(files)
        assert any(f.rule == "MT-D902" for f in findings), [
            f.render() for f in findings]

    def test_spawn_inside_window_is_not_a_yield(self):
        # The semantic pin the whole family rests on: sched.spawn(gen())
        # primes only the NEW task (aio/scheduler.py), so the clean
        # fixture's _dispatch_read — which spawns a generator inside the
        # declared window — must verify (covered by
        # test_clean_fixture_is_silent; asserted here directly).
        from mpit_tpu.analysis import callgraph, disciplines
        from mpit_tpu.analysis.core import collect

        files, _ = collect(CLEANPKG)
        graph = callgraph.build_graph(files)
        section = next(s for s in disciplines.SECTIONS
                       if s.name == "ps-read-snapshot-window")
        assert disciplines.section_findings(graph, section) == []


# -- content-hash suppression keys ------------------------------------------


class TestContentHashBaseline:
    def test_repo_baseline_is_content_keyed(self):
        cfg = load_config(REPO / "mtlint.toml")
        assert all(s.content for s in cfg.suppressions), [
            s.render() for s in cfg.suppressions if not s.content]

    def test_content_key_survives_line_moves(self, tmp_path):
        from mpit_tpu.analysis.core import content_key

        body = (
            "import tags\n"
            "from aio import aio_send\n\n\n"
            "def push_grad(transport, grad):\n"
            "    yield from aio_send(transport, grad, 0, tags.GRAD)\n")
        tagmod = "GRAD = 1\nGRAD_ACK = 2\n" \
                 "TAG_PAIRS = {'GRAD': ('client', 'server'), " \
                 "'GRAD_ACK': ('server', 'client')}\n"
        srv = ("import tags\nfrom aio import aio_recv, aio_send\n\n\n"
               "def serve(transport, buf):\n"
               "    yield from aio_recv(transport, 1, tags.GRAD, out=buf)\n"
               "    yield from aio_send(transport, b'', 1, tags.GRAD_ACK)\n"
               "    yield from aio_recv(transport, 1, tags.GRAD_ACK)\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "tags.py").write_text(tagmod)
        (pkg / "server.py").write_text(srv)
        (pkg / "client.py").write_text(body)
        flagged = ("yield from aio_send(transport, grad, 0, tags.GRAD)")
        key = content_key(flagged)
        (tmp_path / "mtlint.toml").write_text(
            '[[suppress]]\nrule = "MT-P103"\nfile = "pkg/client.py"\n'
            f'content = "{key}"\nreason = "test: content key"\n'
            '[[suppress]]\nrule = "MT-P201"\nfile = "pkg/client.py"\n'
            'line = 6\nreason = "test: line key for the same site"\n'
            '[[suppress]]\nrule = "MT-P201"\nfile = "pkg/server.py"\n'
            'reason = "test: file-wide for the server recv/sends"\n')
        cfg = load_config(tmp_path / "mtlint.toml")
        r1 = run(pkg, cfg)
        assert not [f for f in r1.findings if f.rule == "MT-P103"], [
            f.render() for f in r1.findings]
        # Move the flagged line down 20 lines: the content entry still
        # matches; the line-pinned MT-P201 entry goes stale.
        (pkg / "client.py").write_text(
            "import tags\nfrom aio import aio_send\n" + "\n" * 20 + body)
        cfg = load_config(tmp_path / "mtlint.toml")
        r2 = run(pkg, cfg)
        assert not [f for f in r2.findings if f.rule == "MT-P103"]
        assert [f for f in r2.findings if f.rule == "MT-P201"]
        stale = [s for s in r2.unused_suppressions if s.line == 6]
        assert stale, "line-pinned entry should have gone stale"

    def test_malformed_content_key_rejected(self, tmp_path):
        bad = tmp_path / "mtlint.toml"
        bad.write_text('[[suppress]]\nrule = "MT-C202"\nfile = "x.py"\n'
                       'content = "nothex"\nreason = "r"\n')
        with pytest.raises(ConfigError, match="content"):
            load_config(bad)

    def test_suggest_baseline_prints_content_entries(self):
        r = subprocess.run(
            [sys.executable, "tools/mtlint.py",
             "tests/fixtures/mtlint/badpkg", "--suggest-baseline",
             "--no-config"],
            cwd=str(REPO), capture_output=True, text=True)
        assert r.returncode == 1
        assert "[[suppress]]" in r.stdout
        assert 'content = "' in r.stdout
        # The new families get content-keyed entries like everyone else.
        for rule in ("MT-Y801", "MT-Y802", "MT-Y803",
                     "MT-D901", "MT-D902", "MT-D903"):
            assert f'rule = "{rule}"' in r.stdout, rule

    def test_suggest_baseline_rejects_colliding_content_key(self, tmp_path):
        # An existing baseline entry already claims the content hash of
        # a flagged line (under a different rule, so the finding stays
        # unsuppressed).  Suggesting another content entry with the same
        # key would silently merge the two — the CLI must pin by line
        # instead, loudly.
        from mpit_tpu.analysis.core import content_key

        flagged = (BADPKG / "locks.py").read_text().splitlines()[26]
        key = content_key(flagged)  # locks.py:27 — the MT-C202 seed
        cfg = tmp_path / "mtlint.toml"
        cfg.write_text(
            '[[suppress]]\nrule = "MT-C203"\nfile = "locks.py"\n'
            f'content = "{key}"\n'
            'reason = "test: same content hash claimed by another rule"\n')
        r = subprocess.run(
            [sys.executable, "tools/mtlint.py",
             "tests/fixtures/mtlint/badpkg", "--suggest-baseline",
             "--config", str(cfg)],
            cwd=str(REPO), capture_output=True, text=True)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "already claimed" in r.stdout
        assert f'content = "{key}"' not in r.stdout
        assert "line = 27" in r.stdout
