"""Tier-1 drift gate: every committed SOAK_*/TRACE_*/OBS_* artifact
matches its schema, every doc-referenced Prometheus metric exists in
core/metrics.py and every file a doc names exists
(scripts/check_artifacts.py — the checker the CI story in
doc/observability.md describes)."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import check_artifacts  # noqa: E402


def test_committed_artifacts_match_their_schemas():
    assert check_artifacts.check_artifacts() == []


def test_doc_referenced_metrics_exist():
    assert check_artifacts.check_doc_metrics() == []


def test_doc_named_files_exist():
    """Every source, record and document that README.md or a doc/*.md
    names in backticks is in the tree."""
    assert check_artifacts.check_doc_paths() == []


def test_doc_named_missing_file_is_flagged(tmp_path):
    """The guard actually guards: a document citing a program that has
    gone is flagged; what is there, by path, by package path, by bare
    name or by glob, what the reference holds and what a run writes
    are not."""
    (tmp_path / "doc").mkdir()
    (tmp_path / "scripts").mkdir()
    (tmp_path / "channeld_tpu" / "core").mkdir(parents=True)
    (tmp_path / "scripts" / "a_soak.py").write_text(
        'OUT = "a_report.json"\n')
    (tmp_path / "channeld_tpu" / "core" / "thing_pb2.py").write_text("")
    (tmp_path / "README.md").write_text(
        "`scripts/a_soak.py:12-14` `core/thing_pb2.py` `thing_pb2.py` "
        "`*_pb2.py` `scripts/*_soak.py` `pkg/channeldpb/channeld.proto` "
        "`a_report.json` `scripts/<name>.py` `{a,b}.py`\n")
    assert check_artifacts.check_doc_paths(str(tmp_path)) == []

    (tmp_path / "doc" / "x.md").write_text(
        "run `scripts/gone_driver.py`, read `NOPE_r01.json:3` and "
        "`scripts/*_nope.py`\n")
    errors = check_artifacts.check_doc_paths(str(tmp_path))
    assert len(errors) == 3 and all(e.startswith("doc/x.md") for e in errors)
    assert any("`scripts/gone_driver.py`" in e for e in errors)
    assert any("`NOPE_r01.json`" in e for e in errors)
    assert any("`scripts/*_nope.py`" in e for e in errors)


def test_new_artifact_without_schema_fails(tmp_path):
    """The guard actually guards: an unknown SOAK_*.json is flagged."""
    import json

    (tmp_path / "SOAK_NOVEL_r99.json").write_text(json.dumps({"x": 1}))
    errors = check_artifacts.check_artifacts(str(tmp_path))
    assert any("no schema registered" in e for e in errors)


def test_failing_invariants_artifact_is_flagged(tmp_path):
    import json

    (tmp_path / "SOAK_FED_r99.json").write_text(json.dumps({
        "kind": "federation_soak",
        "invariants": {"ok": False, "checks": []},
        "census": {}, "gateway_a": {}, "gateway_b": {},
        "redirect": {}, "timeline": [],
    }))
    errors = check_artifacts.check_artifacts(str(tmp_path))
    assert any("failing invariants" in e for e in errors)


def test_global_soak_dirty_census_is_flagged(tmp_path):
    """The SOAK_GLOBAL extra checks actually check: key-complete
    artifacts with a dirty adoption census (or no committed migration)
    are flagged even when invariants claim ok."""
    import json

    doc = {
        "kind": "global_soak",
        "invariants": {"ok": True, "checks": [
            {"name": n, "ok": True} for n in (
                "shard_migrations_committed",
                "imbalance_flattened_below_enter",
                "every_entity_on_exactly_one_survivor",
                "a_migrations_ledger_matches_metric",
                "redirect_resumed_on_adopter_without_reauth",
            )
        ]},
        "migration": {"committed": 1},
        "adoption": {}, "redirect": {}, "timeline": [],
        "census": {"missing": [], "duplicated": {"9": 2},
                   "unexpected": []},
    }
    (tmp_path / "SOAK_GLOBAL_r99.json").write_text(json.dumps(doc))
    errors = check_artifacts.check_artifacts(str(tmp_path))
    assert any("census not clean" in e for e in errors)

    doc["census"]["duplicated"] = {}
    doc["migration"]["committed"] = 0
    (tmp_path / "SOAK_GLOBAL_r99.json").write_text(json.dumps(doc))
    errors = check_artifacts.check_artifacts(str(tmp_path))
    assert any("no committed cross-gateway" in e for e in errors)

    doc["invariants"]["checks"] = []
    doc["migration"]["committed"] = 1
    (tmp_path / "SOAK_GLOBAL_r99.json").write_text(json.dumps(doc))
    errors = check_artifacts.check_artifacts(str(tmp_path))
    assert any("missing invariant check" in e for e in errors)


def _device_soak_doc():
    return {
        "kind": "device_soak",
        "invariants": {"ok": True, "checks": [
            {"name": n, "ok": True} for n in (
                "every_entity_in_exactly_one_cell",
                "recovery_within_deadline",
                "device_recoveries_ledger_matches_metric",
                "gateway_never_declared_dead",
                "device_state_active_at_end",
            )
        ]},
        "device": {"state": "ACTIVE",
                   "recovery_counts": {"hang": 1, "corruption": 1}},
        "recoveries": {"worst_s": 0.4, "deadline_s": 10.0},
        "census": {"missing": [], "duplicated": [], "total": 96},
        "scenario": {}, "stats": {},
    }


def test_device_soak_schema_gate(tmp_path):
    """SOAK_DEVICE_*.json extra checks: a clean artifact passes; a dirty
    census, a blown recovery deadline, a run with no rebuild, and a
    missing invariant name are each flagged."""
    import json

    path = tmp_path / "SOAK_DEVICE_r99.json"
    path.write_text(json.dumps(_device_soak_doc()))
    assert check_artifacts.check_artifacts(str(tmp_path)) == []

    doc = _device_soak_doc()
    doc["census"]["duplicated"] = [7]
    path.write_text(json.dumps(doc))
    assert any("census not clean" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _device_soak_doc()
    doc["recoveries"]["worst_s"] = 99.0
    path.write_text(json.dumps(doc))
    assert any("recovery bound not proven" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _device_soak_doc()
    doc["device"]["recovery_counts"] = {"transient": 2}
    path.write_text(json.dumps(doc))
    assert any("no in-process engine rebuild" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _device_soak_doc()
    doc["invariants"]["checks"] = doc["invariants"]["checks"][1:]
    path.write_text(json.dumps(doc))
    assert any("missing invariant check" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))


def _crash_soak_doc():
    return {
        "kind": "crash_soak",
        "invariants": {"ok": True, "checks": [
            {"name": n, "ok": True} for n in (
                "two_crashes",
                "both_kills_mid_handover_burst",
                "zero_committed_entities_lost_or_duplicated",
                "restart_to_serving_within_deadline",
                "replay_within_deadline",
                "torn_tail_replayed",
                "shard_reclaimed_after_restart",
                "shard_yielded_after_restart",
                "a_wal_records_ledger_matches_metric",
            )
        ]},
        "crashes": [
            {"phase": "reclaim", "mid_burst": True, "restart_s": 0.5,
             "torn": False},
            {"phase": "adopt", "mid_burst": True, "restart_s": 0.5,
             "torn": True},
        ],
        "replay": {"torn": True, "elapsed_s": 0.01},
        "resurrection": {"a": {"peer_yielded": 1}, "b": {"yielded": 1}},
        "wal": {"a": {}, "b": {}},
        "census": {"expected": 24, "missing": [], "duplicated": {},
                   "unexpected": []},
    }


def test_crash_soak_schema_gate(tmp_path):
    """SOAK_CRASH_*.json extra checks (doc/persistence.md): a clean
    artifact passes; fewer than two crashes, missing phase coverage, no
    torn-tail replay, a dirty census, and a missing invariant name are
    each flagged."""
    import json

    path = tmp_path / "SOAK_CRASH_r99.json"
    path.write_text(json.dumps(_crash_soak_doc()))
    assert check_artifacts.check_artifacts(str(tmp_path)) == []

    doc = _crash_soak_doc()
    doc["crashes"] = doc["crashes"][:1]
    path.write_text(json.dumps(doc))
    errors = check_artifacts.check_artifacts(str(tmp_path))
    assert any("fewer than 2 crashes" in e for e in errors)
    assert any("missing reclaim/adopt coverage" in e for e in errors)

    doc = _crash_soak_doc()
    for c in doc["crashes"]:
        c["torn"] = False
    path.write_text(json.dumps(doc))
    assert any("no crash replayed a torn WAL tail" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _crash_soak_doc()
    doc["census"]["duplicated"] = {"524289": [["a", 1], ["b", 2]]}
    path.write_text(json.dumps(doc))
    assert any("crash census not clean" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _crash_soak_doc()
    doc["invariants"]["checks"] = [
        c for c in doc["invariants"]["checks"]
        if c["name"] != "torn_tail_replayed"
    ]
    path.write_text(json.dumps(doc))
    assert any("missing invariant check 'torn_tail_replayed'" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))


def _obs_soak_doc():
    return {
        "kind": "obs_soak",
        "invariants": {"ok": True, "checks": [
            {"name": n, "ok": True} for n in (
                "delivery_p99_measured_under_load",
                "delivery_p99_bounded",
                "delivery_p50_bounded",
                "slo_breach_fired",
                "breach_ledger_matches_metric",
                "breach_anomaly_dump_perfetto_valid",
                "readyz_flipped_on_device_fault",
                "healthz_and_introspect_served",
                "staleness_sampled",
                "fleet_digest_exact",
                "obs_overhead_under_2pct",
            )
        ]},
        "delivery": {"p99_ms": 7.9, "p99_under_5ms": False,
                     "steady": {}, "note": "honest"},
        "slo": {"delivery_p99": {}},
        "breaches": {"counts": {"delivery_p99": 1},
                     "ledger_matches_metric": True,
                     "dumps": [{"trigger": "slo_breach",
                                "perfetto_valid": True}]},
        "readyz": {"codes": [200, 503, 200], "flip_ok": True},
        "fleet": {"digest_exact": True, "labelsets_checked": 40},
        "overhead": {"overhead_pct": 0.4},
    }


def test_obs_soak_schema_gate(tmp_path):
    """OBS_*.json extra checks (doc/observability.md): a clean
    artifact passes — including one honestly recording the < 5ms
    verdict as FALSE; a missing p99 record, a missing breach, an
    invalid dump, an unproven digest, a blown overhead bound and a
    missing invariant name are each flagged."""
    import json

    path = tmp_path / "OBS_r99.json"
    path.write_text(json.dumps(_obs_soak_doc()))
    assert check_artifacts.check_artifacts(str(tmp_path)) == []

    doc = _obs_soak_doc()
    del doc["delivery"]["p99_under_5ms"]
    path.write_text(json.dumps(doc))
    assert any("verdict not recorded" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _obs_soak_doc()
    doc["breaches"]["counts"] = {}
    path.write_text(json.dumps(doc))
    assert any("no SLO breach recorded" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _obs_soak_doc()
    doc["breaches"]["dumps"][0]["perfetto_valid"] = False
    path.write_text(json.dumps(doc))
    assert any("breach dumps missing/invalid" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _obs_soak_doc()
    doc["fleet"] = {"digest_exact": False}
    path.write_text(json.dumps(doc))
    assert any("digest exactness not proven" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _obs_soak_doc()
    doc["overhead"]["overhead_pct"] = 3.5
    path.write_text(json.dumps(doc))
    assert any("overhead bound not proven" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _obs_soak_doc()
    doc["invariants"]["checks"] = [
        c for c in doc["invariants"]["checks"]
        if c["name"] != "fleet_digest_exact"
    ]
    path.write_text(json.dumps(doc))
    assert any("missing invariant check 'fleet_digest_exact'" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))


def _density_soak_doc():
    return {
        "kind": "density_soak",
        "invariants": {"ok": True, "checks": [
            {"name": n, "ok": True} for n in (
                "no_geometry_op_while_uniform",
                "pileup_split_committed",
                "steady_density_ratio_below_fixed_grid_floor",
                "partition_metric_matches_ledger",
                "kill_mid_split_aborts_deterministically",
                "split_recommits_after_failover",
                "geometry_restored_after_disperse",
                "device_rebuilds_zero_mismatch",
                "every_entity_in_exactly_one_cell",
                "journal_prepared_equals_committed_plus_aborted",
            )
        ]},
        "partition": {"ledger": {"split_committed": 2, "split_aborted": 1,
                                 "merge_committed": 2}},
        "balancer": {}, "journal": {},
        "kill": {"aborted": True, "epoch_unchanged_by_abort": True,
                 "recommitted_after_failover": True},
        "steady_state": {"density_ratio": 1.09, "max_depth": 1},
        "final_geometry": {"epoch": 4, "splits": []},
        "device_rebuilds": {"verified": 2, "mismatch": 0},
    }


def test_density_soak_schema_gate(tmp_path):
    """SOAK_SPLIT_*.json extra checks (doc/partitioning.md): a clean
    artifact passes; a density ratio at/over the 1.31 fixed-grid
    floor, a missing committed split, unrestored boot geometry, a
    dirty kill record, a device-rebuild mismatch, and a missing
    invariant name are each flagged."""
    import json

    path = tmp_path / "SOAK_SPLIT_r99.json"
    path.write_text(json.dumps(_density_soak_doc()))
    assert check_artifacts.check_artifacts(str(tmp_path)) == []

    doc = _density_soak_doc()
    doc["steady_state"]["density_ratio"] = 1.45
    path.write_text(json.dumps(doc))
    assert any("1.31 fixed-grid floor" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _density_soak_doc()
    doc["partition"]["ledger"]["split_committed"] = 0
    path.write_text(json.dumps(doc))
    assert any("no committed live split" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _density_soak_doc()
    doc["final_geometry"]["splits"] = [65541]
    path.write_text(json.dumps(doc))
    assert any("boot geometry not restored" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _density_soak_doc()
    doc["kill"]["epoch_unchanged_by_abort"] = False
    path.write_text(json.dumps(doc))
    assert any("kill-mid-split record not clean" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _density_soak_doc()
    doc["device_rebuilds"]["mismatch"] = 1
    path.write_text(json.dumps(doc))
    assert any("device rebuild verification not clean" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _density_soak_doc()
    doc["invariants"]["checks"] = [
        c for c in doc["invariants"]["checks"]
        if c["name"] != "split_recommits_after_failover"
    ]
    path.write_text(json.dumps(doc))
    assert any("missing invariant check 'split_recommits_after_failover'"
               in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))


def test_partitioning_doc_matches_declared_knobs():
    """doc/partitioning.md documents exactly the partition_* knobs
    core/settings.py declares, and the planes the geometry epochs ride
    (README, balancer, global control, persistence) cross-link it."""
    assert check_artifacts.check_partitioning_doc() == []


def test_partitioning_doc_drift_is_flagged(tmp_path):
    import shutil

    doc_dir = tmp_path / "doc"
    doc_dir.mkdir()
    core = tmp_path / "channeld_tpu" / "core"
    core.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "channeld_tpu", "core", "settings.py"),
                core / "settings.py")

    errors = check_artifacts.check_partitioning_doc(str(tmp_path))
    assert errors and "missing" in errors[0]

    (doc_dir / "partitioning.md").write_text(
        "# x\n\n`partition_enabled` and the phantom `partition_ghost_knob`.\n"
    )
    errors = check_artifacts.check_partitioning_doc(str(tmp_path))
    # Every undeclared documented knob + every undocumented declared
    # knob + all four missing cross-links are flagged.
    assert any("partition_ghost_knob" in e for e in errors)
    assert any("partition_max_depth" in e for e in errors)
    assert sum("no cross-link" in e for e in errors) == 4


def test_artifact_metric_refs_are_checked():
    """Committed artifacts citing metrics must cite registered families
    with the declared label sets (scripts/check_artifacts.py
    check_artifact_metrics)."""
    assert check_artifacts.check_artifact_metrics() == []


def test_doc_metric_label_set_mismatch_is_flagged():
    """The label-set validation actually validates: a doc citing a
    label the declaration does not carry (or a label VALUE where the
    label NAME belongs) is drift."""
    names = {"overload_sheds", "tick_stage_ms"}
    label_sets = {"overload_sheds": {"reason"}, "tick_stage_ms": {"stage"}}
    errors = check_artifacts._check_metric_refs(
        "doc/x.md", set(),
        [("overload_sheds_total", "cause"),       # wrong label name
         ("tick_stage_ms", "trunk"),              # label value, not name
         ("overload_sheds_total", 'reason="handover_defer"')],  # ok
        names, label_sets,
    )
    assert len(errors) == 2
    assert any("overload_sheds" in e and "['cause']" in e for e in errors)
    assert any("tick_stage_ms" in e and "['trunk']" in e for e in errors)


def test_artifact_braced_metric_ref_with_bad_label_is_flagged(tmp_path):
    import json

    (tmp_path / "SOAK_r99.json").write_text(json.dumps({
        "kind": "chaos_soak", "scenario": {}, "stats": {},
        "duration_s": 1, "invariants": {"ok": True, "checks": []},
        "note": 'ledger matches overload_sheds_total{cause}',
    }))
    errors = check_artifacts.check_artifact_metrics(str(tmp_path))
    assert any("overload_sheds" in e and "['cause']" in e for e in errors)


def test_doc_metric_exposition_pairs_accepted():
    """name{label=\"value\"} exposition-style refs resolve to the label
    NAME (the doc/federation.md fix this check forced stays fixed)."""
    assert check_artifacts._parse_ref_labels('trigger="handover_abort"') \
        == {"trigger"}
    assert check_artifacts._parse_ref_labels("cell,direction") \
        == {"cell", "direction"}


def test_artifact_quoted_exposition_ref_is_validated(tmp_path):
    """Exposition-style refs with JSON-escaped quoted values
    (backend=\\"host\\") are parsed and validated — a quoted ref with a
    stale label name is flagged, a correct one passes."""
    import json

    base = {
        "kind": "chaos_soak", "scenario": {}, "stats": {},
        "duration_s": 1, "invariants": {"ok": True, "checks": []},
    }
    good = dict(base, note='feeds fanout_decision_latency_seconds'
                           '{backend="host"}')
    (tmp_path / "SOAK_r98.json").write_text(json.dumps(good))
    assert check_artifacts.check_artifact_metrics(str(tmp_path)) == []

    bad = dict(base, note='feeds fanout_decision_latency_seconds'
                          '{chip="host"}')
    (tmp_path / "SOAK_r98.json").write_text(json.dumps(bad))
    errors = check_artifacts.check_artifact_metrics(str(tmp_path))
    assert any("fanout_decision_latency_seconds" in e and "['chip']" in e
               for e in errors)


def test_concurrency_doc_matches_thread_model():
    """doc/concurrency.md documents exactly the execution domains
    analysis/threadmodel.py declares (doc/concurrency.md is the
    operator's map; drift in either direction fails)."""
    assert check_artifacts.check_concurrency_doc() == []


def test_concurrency_doc_drift_is_flagged(tmp_path):
    doc_dir = tmp_path / "doc"
    doc_dir.mkdir()
    (doc_dir / "concurrency.md").write_text(
        "# x\n\n### `tick-loop`\n\n### `ghost-domain`\n"
    )
    errors = check_artifacts.check_concurrency_doc(str(tmp_path))
    # Every undocumented declared domain + the phantom section flag.
    assert any("ghost-domain" in e for e in errors)
    assert any("wal-writer" in e for e in errors)


def test_missing_concurrency_doc_is_flagged(tmp_path):
    (tmp_path / "doc").mkdir()
    errors = check_artifacts.check_concurrency_doc(str(tmp_path))
    assert errors and "missing" in errors[0]


def test_query_engine_doc_matches_declared_knobs():
    """doc/query_engine.md documents exactly the queryplane_* knobs
    core/settings.py declares, and the planes the standing-query
    registry rides (README, observability, partitioning, device
    recovery) cross-link it."""
    assert check_artifacts.check_query_engine_doc() == []


def test_query_engine_doc_drift_is_flagged(tmp_path):
    import shutil

    doc_dir = tmp_path / "doc"
    doc_dir.mkdir()
    core = tmp_path / "channeld_tpu" / "core"
    core.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "channeld_tpu", "core", "settings.py"),
                core / "settings.py")

    errors = check_artifacts.check_query_engine_doc(str(tmp_path))
    assert errors and "missing" in errors[0]

    (doc_dir / "query_engine.md").write_text(
        "# x\n\n`queryplane_enabled` and the phantom "
        "`queryplane_ghost_knob`.\n"
    )
    errors = check_artifacts.check_query_engine_doc(str(tmp_path))
    assert any("queryplane_ghost_knob" in e for e in errors)
    assert any("queryplane_rows_max" in e for e in errors)
    assert sum("no cross-link" in e for e in errors) == 4


def _sim_soak_doc():
    names = [
        "steady: census transfer double-entry",
        "stampede: crossings flowed through ordinary handover",
        "guard: sim rebuild double-entry",
        "kill9: restored census bit-identical to last journaled",
        "kill9: replay counter double-entry",
    ]
    for phase in ("steady", "stampede", "guard", "epoch", "kill9"):
        names.append(f"{phase}: zero agents lost from cell tables")
        names.append(f"{phase}: zero agents duplicated in cell tables")
    return {
        "kind": "sim_soak",
        "seed": 1,
        "agents": 96,
        "humans": 16,
        "duration_s": 1.0,
        "phases": {
            "steady": {}, "stampede": {}, "guard": {}, "epoch": {},
            "kill9": {"restored_hash": "ab" * 32},
        },
        "invariants": {
            "ok": True,
            "checks": [{"name": n, "ok": True, "detail": ""}
                       for n in names],
        },
    }


def test_sim_soak_schema_gate(tmp_path):
    """SOAK_SIM_*.json extra checks (doc/simulation.md): a clean
    artifact passes; a missing phase, a kill -9 record without the
    bit-identical restored-census hash, and a dropped exact-census
    invariant are each flagged."""
    import json

    path = tmp_path / "SOAK_SIM_r99.json"
    path.write_text(json.dumps(_sim_soak_doc()))
    assert check_artifacts.check_artifacts(str(tmp_path)) == []

    doc = _sim_soak_doc()
    del doc["phases"]["epoch"]
    path.write_text(json.dumps(doc))
    assert any("phase 'epoch' missing" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _sim_soak_doc()
    doc["phases"]["kill9"] = {}
    path.write_text(json.dumps(doc))
    assert any("no restored census hash" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))

    doc = _sim_soak_doc()
    doc["invariants"]["checks"] = [
        c for c in doc["invariants"]["checks"]
        if c["name"] != "kill9: zero agents lost from cell tables"
    ]
    path.write_text(json.dumps(doc))
    assert any("missing invariant check "
               "'kill9: zero agents lost from cell tables'" in e
               for e in check_artifacts.check_artifacts(str(tmp_path)))


def test_simulation_doc_matches_declared_knobs():
    """doc/simulation.md's knob table documents exactly the sim_*
    knobs core/settings.py declares, and the planes the population
    rides (README, device recovery, query engine, chaos) cross-link
    it."""
    assert check_artifacts.check_simulation_doc() == []


def test_simulation_doc_drift_is_flagged(tmp_path):
    import shutil

    doc_dir = tmp_path / "doc"
    doc_dir.mkdir()
    core = tmp_path / "channeld_tpu" / "core"
    core.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "channeld_tpu", "core", "settings.py"),
                core / "settings.py")

    errors = check_artifacts.check_simulation_doc(str(tmp_path))
    assert errors and "missing" in errors[0]

    (doc_dir / "simulation.md").write_text(
        "# x\n\n| `sim_enabled` | `false` | on |\n"
        "| `sim_ghost_knob` | `1` | phantom |\n"
        "\nthe `sim_pass_ms` metric is NOT a knob\n"
    )
    errors = check_artifacts.check_simulation_doc(str(tmp_path))
    # Every undeclared table row + every declared-but-untabled knob +
    # all four missing cross-links are flagged; a metric reference
    # outside the table is NOT mistaken for a knob.
    assert any("sim_ghost_knob" in e for e in errors)
    assert any("sim_census_every_ticks" in e for e in errors)
    assert not any("sim_pass_ms" in e for e in errors)
    assert sum("no cross-link" in e for e in errors) == 4
