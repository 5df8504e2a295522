"""Artifact + doc drift checker (run from tier-1: tests/test_artifacts.py).

Three classes of silent rot this repo has accumulated defenses against,
now checked in one place on every test run:

1. **Committed artifacts** — every ``SOAK_*.json`` / ``TRACE_*.json`` /
   ``OBS_*.json`` at the repo root must parse and match its schema
   (the required keys its soak writer emits and its README/docs
   claims cite). A soak refactor that silently changes an artifact's
   shape fails here instead of when a reviewer re-reads the claim.
   Speed is not recorded here: the benchmark (``benchmark/``) and the
   driver's ``PERF_LEDGER.jsonl`` are its one record.
2. **Doc'd metric names** — every Prometheus metric a doc or the README
   references must exist in ``core/metrics.py``. Renaming a metric
   without fixing the docs (or documenting a metric that was never
   registered) fails fast.
3. **Doc'd files** — every source, record or document a doc or the
   README names in backticks must exist: a document may not cite a
   program that has gone.

Usage: ``python scripts/check_artifacts.py`` (exit 0 = clean).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# ---------------------------------------------------------------------------
# artifact schemas: filename glob -> required top-level keys (+ checks)
# ---------------------------------------------------------------------------

# Every soak artifact is written by an InvariantChecker-driven harness:
# it must carry its kind tag and a PASSING invariants summary — a
# committed artifact documenting a failed run is drift by definition.
_SOAK_KEYS = {"kind", "invariants"}

SCHEMAS: dict[str, set] = {
    "SOAK_r*.json": _SOAK_KEYS | {"scenario", "stats", "duration_s"},
    "SOAK_OVERLOAD_*.json": _SOAK_KEYS | {"governor", "phases", "max_level"},
    "SOAK_FAILOVER_*.json": _SOAK_KEYS | {"failover", "journal", "kills"},
    "SOAK_BALANCE_*.json": _SOAK_KEYS | {"balancer", "journal", "kill"},
    "SOAK_FED_*.json": _SOAK_KEYS | {
        "census", "gateway_a", "gateway_b", "redirect", "timeline",
    },
    "SOAK_GLOBAL_*.json": _SOAK_KEYS | {
        "migration", "adoption", "redirect", "census",
    },
    # Device supervision soak (doc/device_recovery.md acceptance
    # artifact): the guard's recovery ledger, the census, and the
    # bounded-recovery numbers the doc cites.
    "SOAK_DEVICE_*.json": _SOAK_KEYS | {
        "device", "recoveries", "census", "scenario", "stats",
    },
    # Flight-recorder soak (doc/observability.md acceptance artifact).
    "TRACE_*.json": _SOAK_KEYS | {
        "stages", "anomaly_dumps", "cross_gateway", "overhead",
    },
    # Crash-restart soak (doc/persistence.md acceptance artifact): the
    # kill -9 timeline, the boot-replay report, the resurrection
    # outcomes, and the WAL double-entry ledgers.
    "SOAK_CRASH_*.json": _SOAK_KEYS | {
        "crashes", "replay", "resurrection", "wal", "census",
    },
    # Fleet health plane soak (doc/observability.md acceptance
    # artifact): live delivery p99 with the < 5ms verdict recorded
    # honestly, SLO breach + dump evidence, the /readyz flip matrix,
    # fleet digest exactness, and the plane overhead bound.
    "OBS_*.json": _SOAK_KEYS | {
        "delivery", "slo", "breaches", "readyz", "fleet", "overhead",
    },
    # Adversarial edge soak (doc/edge_hardening.md acceptance
    # artifact): the three concurrent attacker classes, the edge
    # ledgers, the honest census/delivery accounting, and the RSS bound.
    "SOAK_ABUSE_*.json": _SOAK_KEYS | {
        "attackers", "edge", "census", "delivery", "rss",
    },
    # On-device simulation soak (doc/simulation.md acceptance
    # artifact): exact census (zero agents lost or duplicated) across
    # the steady / stampede / guard-rebuild / geometry-epoch / kill -9
    # phases, with the restored population bit-identical to the last
    # journaled census.
    "SOAK_SIM_*.json": _SOAK_KEYS | {"phases", "agents", "seed"},
    # Adaptive-partitioning density soak (doc/partitioning.md
    # acceptance artifact): the geometry ledgers, the kill-mid-split
    # record, the steady-state density fold, the final geometry, and
    # the device rebuild verification counts.
    "SOAK_SPLIT_*.json": _SOAK_KEYS | {
        "partition", "balancer", "kill", "steady_state",
        "final_geometry", "device_rebuilds", "journal",
    },
}


def _check_global_soak(doc: dict) -> list[str]:
    """The global-control soak's acceptance bar, pinned beyond key
    presence: the invariant list must actually contain the migration /
    exactly-one-survivor / ledger==metrics / redirect-resume checks
    (doc/global_control.md), and the adoption census must be clean."""
    errors: list[str] = []
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
        "shard_migrations_committed",
        "imbalance_flattened_below_enter",
        "every_entity_on_exactly_one_survivor",
        "redirect_resumed_on_adopter_without_reauth",
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    if not any(n and n.endswith("_ledger_matches_metric") for n in names):
        errors.append("no ledger==metrics invariant checks")
    census = doc.get("census", {})
    if census.get("missing") or census.get("duplicated") \
            or census.get("unexpected"):
        errors.append(f"adoption census not clean: {census}")
    if not doc.get("migration", {}).get("committed"):
        errors.append("no committed cross-gateway shard migration")
    return errors


def _check_device_soak(doc: dict) -> list[str]:
    """The device-recovery soak's acceptance bar beyond key presence
    (doc/device_recovery.md): zero-loss census, bounded recovery,
    ledger==metrics, no death declaration — and the engine actually
    rebuilt in-process (a run where no rebuild happened proves
    nothing)."""
    errors: list[str] = []
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
        "every_entity_in_exactly_one_cell",
        "recovery_within_deadline",
        "device_recoveries_ledger_matches_metric",
        "gateway_never_declared_dead",
        "device_state_active_at_end",
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    census = doc.get("census", {})
    if census.get("missing") or census.get("duplicated"):
        errors.append(f"entity census not clean: {census}")
    counts = doc.get("device", {}).get("recovery_counts", {})
    if not (counts.get("hang") or counts.get("corruption")
            or counts.get("step_error")):
        errors.append("no in-process engine rebuild recorded "
                      f"(recovery_counts={counts})")
    worst = doc.get("recoveries", {}).get("worst_s")
    deadline = doc.get("recoveries", {}).get("deadline_s")
    if worst is None or deadline is None or worst > deadline:
        errors.append(
            f"recovery bound not proven (worst={worst}, "
            f"deadline={deadline})"
        )
    return errors


def _check_crash_soak(doc: dict) -> list[str]:
    """The crash soak's acceptance bar beyond key presence
    (doc/persistence.md): >= 2 kill -9 crashes mid-handover-burst with
    one shard adopted and one reclaimed, zero committed entities lost
    or duplicated fleet-wide, restart-to-serving bounded, a torn WAL
    tail replayed past truncation, and wal/resurrection ledger==metric
    invariants present."""
    errors: list[str] = []
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
        "both_kills_mid_handover_burst",
        "zero_committed_entities_lost_or_duplicated",
        "restart_to_serving_within_deadline",
        "replay_within_deadline",
        "torn_tail_replayed",
        "shard_reclaimed_after_restart",
        "shard_yielded_after_restart",
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    if not any(n and n.endswith("_ledger_matches_metric") for n in names):
        errors.append("no ledger==metrics invariant checks")
    crashes = doc.get("crashes", [])
    if len(crashes) < 2:
        errors.append(f"fewer than 2 crashes recorded ({len(crashes)})")
    phases = {c.get("phase") for c in crashes}
    if not {"reclaim", "adopt"} <= phases:
        errors.append(f"crash phases {sorted(phases)} missing "
                      "reclaim/adopt coverage")
    if not any(c.get("torn") for c in crashes):
        errors.append("no crash replayed a torn WAL tail")
    census = doc.get("census", {})
    if census.get("missing") or census.get("duplicated") \
            or census.get("unexpected"):
        errors.append(f"crash census not clean: {census}")
    return errors


def _check_obs_soak(doc: dict) -> list[str]:
    """The obs soak's acceptance bar beyond key presence
    (doc/observability.md): delivery p99 measured AND the < 5ms
    verdict recorded (true or false — honesty, not success, is
    gated), at least one injected breach with a Perfetto-valid dump
    and exact double-entry, fleet digest exactness, the /readyz flip,
    and plane overhead < 2%."""
    errors: list[str] = []
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
        "delivery_p99_measured_under_load",
        "delivery_p99_bounded",
        "delivery_p50_bounded",
        "slo_breach_fired",
        "breach_ledger_matches_metric",
        "breach_anomaly_dump_perfetto_valid",
        "readyz_flipped_on_device_fault",
        "fleet_digest_exact",
        "obs_overhead_under_2pct",
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    delivery = doc.get("delivery", {})
    if "p99_under_5ms" not in delivery or "p99_ms" not in delivery:
        errors.append("delivery p99 / <5ms verdict not recorded")
    breaches = doc.get("breaches", {})
    if not breaches.get("counts"):
        errors.append("no SLO breach recorded")
    dumps = breaches.get("dumps", [])
    if not dumps or not all(d.get("perfetto_valid") for d in dumps):
        errors.append(f"breach dumps missing/invalid: {dumps}")
    if not doc.get("fleet", {}).get("digest_exact"):
        errors.append("fleet digest exactness not proven")
    overhead = doc.get("overhead", {}).get("overhead_pct")
    if overhead is None or overhead > 2.0:
        errors.append(f"plane overhead bound not proven ({overhead})")
    return errors


def _check_abuse_soak(doc: dict) -> list[str]:
    """The abuse soak's acceptance bar beyond key presence
    (doc/edge_hardening.md): >= 3 CONCURRENT attacker classes, honest
    census exact with delivery accounting intact, every slow reader
    walked to a structured disconnect, every flood source banned, all
    four edge ledgers double-entried against their metrics, and RSS
    bounded across the attack."""
    errors: list[str] = []
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
        "honest_census_exact",
        "honest_delivery_exact",
        "slow_readers_structurally_disconnected",
        "malformed_counted_at_framing",
        "flood_sources_banned",
        "rss_growth_bounded_mb",
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    ledger_checks = {n for n in names if n and n.endswith("_ledger_matches_metric")}
    if len(ledger_checks) < 4:
        errors.append("fewer than 4 ledger==metric invariant checks "
                      f"({sorted(ledger_checks)})")
    classes = doc.get("attackers", {}).get("classes", [])
    if len(classes) < 3:
        errors.append(f"fewer than 3 attacker classes ({classes})")
    census = doc.get("census", {})
    if census.get("survivors") != census.get("expected") \
            or census.get("honest_disconnects"):
        errors.append(f"honest census not clean: {census}")
    delivery = doc.get("delivery", {})
    if delivery.get("missing") or not delivery.get("frames_sent"):
        errors.append(f"delivery accounting not clean: {delivery}")
    rss = doc.get("rss", {})
    if rss.get("growth_mb") is None or rss.get("bound_mb") is None \
            or rss["growth_mb"] > rss["bound_mb"]:
        errors.append(f"rss bound not proven: {rss}")
    return errors


def _check_density_soak(doc: dict) -> list[str]:
    """The density soak's acceptance bar beyond key presence
    (doc/partitioning.md): at least one committed LIVE split with the
    steady per-server max/mean flattened below the 1.31 fixed-grid
    floor, exactly-once placement, partition_ops_total == the python
    ledger, the injected kill aborted deterministically (geometry epoch
    untouched) with the re-planned split committing after failover,
    cold merges restoring the boot geometry, and every device
    micro-grid rebuild verified bit-identical (zero mismatches)."""
    errors: list[str] = []
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
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
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    steady = doc.get("steady_state", {})
    ratio = steady.get("density_ratio")
    if ratio is None or ratio > 1.31:
        errors.append(
            f"steady density ratio not under the 1.31 fixed-grid floor "
            f"({ratio})"
        )
    if not steady.get("max_depth"):
        errors.append("no live split depth recorded at steady state")
    ledger = doc.get("partition", {}).get("ledger", {})
    if not ledger.get("split_committed"):
        errors.append(f"no committed live split (ledger={ledger})")
    if not ledger.get("merge_committed"):
        errors.append(f"no committed cold merge (ledger={ledger})")
    if doc.get("final_geometry", {}).get("splits"):
        errors.append(
            f"boot geometry not restored: {doc['final_geometry']}"
        )
    kill = doc.get("kill") or {}
    if not (kill.get("aborted") and kill.get("epoch_unchanged_by_abort")
            and kill.get("recommitted_after_failover")):
        errors.append(f"kill-mid-split record not clean: {kill}")
    rebuilds = doc.get("device_rebuilds", {})
    if rebuilds.get("mismatch") != 0 or not rebuilds.get("verified"):
        errors.append(f"device rebuild verification not clean: {rebuilds}")
    return errors


def _check_sim_soak(doc: dict) -> list[str]:
    """The sim soak's acceptance bar beyond key presence
    (doc/simulation.md): all five phases ran, the kill -9 phase
    carries the bit-identical restored-census evidence, and the
    zero-loss census held at every phase boundary."""
    errors: list[str] = []
    phases = doc.get("phases", {})
    for required in ("steady", "stampede", "guard", "epoch", "kill9"):
        if required not in phases:
            errors.append(f"phase {required!r} missing")
    if not phases.get("kill9", {}).get("restored_hash"):
        errors.append("kill9 phase has no restored census hash")
    names = {
        c.get("name") for c in doc.get("invariants", {}).get("checks", [])
    }
    for required in (
        "kill9: restored census bit-identical to last journaled",
        "kill9: replay counter double-entry",
        "steady: census transfer double-entry",
        "guard: sim rebuild double-entry",
        "stampede: crossings flowed through ordinary handover",
    ):
        if required not in names:
            errors.append(f"missing invariant check {required!r}")
    for phase in ("steady", "stampede", "guard", "epoch", "kill9"):
        for kind in ("lost from", "duplicated in"):
            check = f"{phase}: zero agents {kind} cell tables"
            if check not in names:
                errors.append(f"missing invariant check {check!r}")
    return errors


EXTRA_CHECKS = {
    "SOAK_GLOBAL_*.json": _check_global_soak,
    "SOAK_DEVICE_*.json": _check_device_soak,
    "SOAK_CRASH_*.json": _check_crash_soak,
    "OBS_*.json": _check_obs_soak,
    "SOAK_ABUSE_*.json": _check_abuse_soak,
    "SOAK_SPLIT_*.json": _check_density_soak,
    "SOAK_SIM_*.json": _check_sim_soak,
}


def _artifact_paths(repo: str) -> list[str]:
    """Every root file that looks like a pinned record."""
    return sorted(
        path for pattern in ("SOAK_*.json", "TRACE_*.json", "OBS_*.json")
        for path in glob.glob(os.path.join(repo, pattern)))


def check_artifacts(repo: str = REPO) -> list[str]:
    errors: list[str] = []
    matched: set[str] = set()
    for pattern, required in SCHEMAS.items():
        for path in sorted(glob.glob(os.path.join(repo, pattern))):
            name = os.path.basename(path)
            matched.add(name)
            try:
                doc = json.load(open(path))
            except ValueError as e:
                errors.append(f"{name}: unparseable JSON ({e})")
                continue
            if not isinstance(doc, dict):
                errors.append(f"{name}: expected a JSON object")
                continue
            missing = required - set(doc)
            if missing:
                errors.append(f"{name}: missing keys {sorted(missing)}")
            inv = doc.get("invariants")
            if "invariants" in required and isinstance(inv, dict):
                if not inv.get("ok", False):
                    errors.append(
                        f"{name}: committed with failing invariants"
                    )
            extra = EXTRA_CHECKS.get(pattern)
            if extra is not None and not missing:
                errors.extend(f"{name}: {e}" for e in extra(doc))
    # Nothing at the root may LOOK like a pinned artifact yet escape
    # every schema (a new SOAK_X_rNN.json must land with a schema row).
    for path in _artifact_paths(repo):
        name = os.path.basename(path)
        if name not in matched:
            errors.append(f"{name}: no schema registered in "
                          f"scripts/check_artifacts.py")
    return errors


# ---------------------------------------------------------------------------
# doc'd metric names vs core/metrics.py
# ---------------------------------------------------------------------------

# Docs scanned for metric references. Counters appear as `name_total`
# (the exposition-format name); labeled histograms/gauges as
# `name{label}`. Bare `_ms`/`_seconds` tokens are NOT scanned — they
# collide with settings knobs (`federation_heartbeat_ms` is a flag, not
# a metric), and every labeled family the docs cite hits the braced
# form anyway.
DOC_GLOBS = ("doc/*.md", "README.md")

_TOTAL_RE = re.compile(r"\b([a-z][a-z0-9_]*)_total\b")
_BRACED_RE = re.compile(r"`([a-z][a-z0-9_]*)\{([a-zA-Z_0-9,=\" ]*)\}`")
# Braced refs inside committed artifact JSON appear within string
# values ("... overload_sheds_total{reason} ..."), where exposition
# pairs carry JSON-escaped quotes (backend=\"host\"). The name must
# abut the brace and the label text allows no bare quote or brace, so
# JSON structure itself ("stats": {...}) can never match.
# no lookbehind char may extend the name or be a backslash: embedded
# stdout in an artifact contains escaped "\n{...}" sequences
# whose 'n' would otherwise read as a one-letter metric name.
_ARTIFACT_BRACED_RE = re.compile(
    r'(?<![A-Za-z0-9_\\])([a-z][a-z0-9_]*)\{((?:[^}{"\\\n]|\\")+)\}')


def registered_metric_names() -> set[str]:
    from channeld_tpu.core.metrics import registry

    names = set()
    for family in registry.collect():
        names.add(family.name)
    return names


def registered_label_sets() -> dict[str, set[str]]:
    """{family name: declared label names} for every metric object in
    core/metrics.py (a labelless family maps to an empty set)."""
    from channeld_tpu.core import metrics as m

    out: dict[str, set[str]] = {}
    for obj in vars(m).values():
        name = getattr(obj, "_name", None)
        labels = getattr(obj, "_labelnames", None)
        if isinstance(name, str) and labels is not None:
            out[name] = set(labels)
    return out


def _parse_ref_labels(inner: str) -> set[str]:
    """Label names from the inside of a ``name{...}`` reference —
    either bare names (``stage``, ``cell,direction``) or exposition
    pairs (``reason="handover_defer"``)."""
    labels: set[str] = set()
    for part in inner.split(","):
        part = part.strip()
        if not part:
            continue
        labels.add(part.split("=", 1)[0].strip().strip('"'))
    return labels


def _check_metric_refs(
    where: str, totals: set[str], braced: list[tuple[str, str]],
    names: set[str], label_sets: dict[str, set[str]],
) -> list[str]:
    """Shared doc/artifact validation: every referenced family exists
    and every braced reference cites EXACTLY the declared label set
    (a doc citing a stale label drifts silently otherwise)."""
    errors: list[str] = []
    refs: set[str] = set(totals)
    for base, _ in braced:
        refs.add(base[:-6] if base.endswith("_total") else base)
    for ref in sorted(refs):
        if ref in names:
            continue
        # /fleet families are the registered families under a fleet_
        # prefix (federation/obs.py render_prometheus): a fleet_X ref
        # is valid exactly when X is registered; the fleet_-native
        # summary gauges (fleet_gateways, fleet_gateway_up, ...) are
        # synthesized and carry no base family.
        if ref.startswith("fleet_") and (
            ref[len("fleet_"):] in names
            or ref in ("fleet_gateways", "fleet_gateway_up",
                       "fleet_gateway_overload_level",
                       "fleet_gateway_pressure", "fleet_gateway_entities",
                       "fleet_gateway_cells", "fleet_leader",
                       "fleet_shard_block", "fleet_shard_override",
                       "fleet_directory_version")
        ):
            continue
        errors.append(
            f"{where}: references metric {ref!r} not registered in "
            f"core/metrics.py"
        )
    for base, inner in braced:
        family = base[:-6] if base.endswith("_total") else base
        declared = label_sets.get(family)
        if declared is None:
            continue  # unknown family already reported above
        used = _parse_ref_labels(inner)
        if used != declared:
            errors.append(
                f"{where}: metric {family!r} referenced with labels "
                f"{sorted(used)} but core/metrics.py declares "
                f"{sorted(declared)}"
            )
    return errors


def check_doc_metrics(repo: str = REPO) -> list[str]:
    names = registered_metric_names()
    label_sets = registered_label_sets()
    errors: list[str] = []
    for pattern in DOC_GLOBS:
        for path in sorted(glob.glob(os.path.join(repo, pattern))):
            text = open(path).read()
            errors.extend(_check_metric_refs(
                os.path.relpath(path, repo),
                set(_TOTAL_RE.findall(text)),
                _BRACED_RE.findall(text),
                names, label_sets,
            ))
    return errors


def check_artifact_metrics(repo: str = REPO) -> list[str]:
    """Metric references inside committed soak and trace artifacts
    (invariant-check names cite families with their label sets) must
    also exist and carry the declared labels."""
    names = registered_metric_names()
    label_sets = registered_label_sets()
    errors: list[str] = []
    for path in _artifact_paths(repo):
        text = open(path).read()
        braced = _ARTIFACT_BRACED_RE.findall(text)
        # Artifacts carry free-form soak-local stat keys that may
        # end in _total; only braced refs (deliberate metric
        # citations, label set included) and bare _total tokens
        # matching a registered family are validated.
        totals = {
            base for base in _TOTAL_RE.findall(text) if base in names
        }
        errors.extend(_check_metric_refs(
            os.path.basename(path), totals, braced, names, label_sets,
        ))
    return errors


# ---------------------------------------------------------------------------
# doc'd files vs the tree
# ---------------------------------------------------------------------------

_PATH_RE = re.compile(
    r"`([A-Za-z0-9_.*/-]+\.(?:py|json|md|sh|cc|proto))(?::[0-9][0-9,-]*)?`")
# Where the upstream Go project keeps its sources (SURVEY.md): a path
# under one of these cites the reference, which is not part of this tree.
_REFERENCE_DIRS = ("pkg/", "cmd/")
_SKIP_DIRS = {"chiprun_out", "profiles", "build", "__pycache__"}


def _tree_files(repo: str) -> tuple[list[str], str]:
    """(every file git would commit, the text of its programs): what a
    bare name may resolve against. Walked, not asked of git: a checkout
    under test need not be a repository."""
    names: list[str] = []
    sources: list[str] = []
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in _SKIP_DIRS]
        names.extend(files)
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name), errors="replace") as f:
                    sources.append(f.read())
    return names, "\n".join(sources)


def check_doc_paths(repo: str = REPO) -> list[str]:
    """Every ``*.py``, ``*.json``, ``*.md``, ``*.sh``, ``*.cc`` and
    ``*.proto`` file that ``README.md`` or a ``doc/*.md`` names in
    backticks exists: from the root, or under ``channeld_tpu/``, or, a
    bare name or bare glob, anywhere in the tree. Two kinds of name are
    rightly absent: a path in the reference's tree, and a bare name
    that a program of this tree writes at run time (it stands quoted in
    that program's source)."""
    import fnmatch

    names, sources = _tree_files(repo)
    errors: list[str] = []
    for pattern in DOC_GLOBS:
        for path in sorted(glob.glob(os.path.join(repo, pattern))):
            with open(path) as f:
                cited = sorted(set(_PATH_RE.findall(f.read())))
            for token in cited:
                if token.startswith(_REFERENCE_DIRS):
                    continue
                if glob.glob(os.path.join(repo, token)) or glob.glob(
                        os.path.join(repo, "channeld_tpu", token)):
                    continue
                if "/" not in token and (
                        fnmatch.filter(names, token)
                        or f'"{token}"' in sources):
                    continue
                errors.append(
                    f"{os.path.relpath(path, repo)}: names `{token}`, "
                    "which is not in the tree")
    return errors


def check_concurrency_doc(repo: str = REPO) -> list[str]:
    """doc/concurrency.md must document exactly the execution domains
    the thread model declares (analysis/threadmodel.py DOMAINS) — the
    doc is the operator's map of the threading discipline, and a
    domain added without documentation (or documented after removal)
    is drift. Gate input: the same per-domain table scripts/analyze.py
    --json exports as ``domains``."""
    from channeld_tpu.analysis.threadmodel import DOMAINS

    path = os.path.join(repo, "doc", "concurrency.md")
    if not os.path.exists(path):
        return ["doc/concurrency.md missing (execution-domain reference "
                "for analysis/threadmodel.py)"]
    text = open(path).read()
    errors: list[str] = []
    documented = set(re.findall(r"^###\s+`([a-z-]+)`", text, re.M))
    declared = {d.name for d in DOMAINS}
    for name in sorted(declared - documented):
        errors.append(
            f"doc/concurrency.md: domain {name!r} is declared in "
            "analysis/threadmodel.py but has no '### `<domain>`' section"
        )
    for name in sorted(documented - declared):
        errors.append(
            f"doc/concurrency.md: section for domain {name!r} has no "
            "matching declaration in analysis/threadmodel.py DOMAINS"
        )
    return errors


def check_partitioning_doc(repo: str = REPO) -> list[str]:
    """doc/partitioning.md must document every ``partition_*`` operator
    knob core/settings.py declares (a knob added without doc — or
    documented after removal — is drift), and the docs whose planes the
    geometry epochs ride must cross-link it: README, doc/balancer.md
    (shared freeze/migration machinery), doc/global_control.md
    (geometry anti-entropy), doc/persistence.md (WAL geometry records
    + replay re-homing)."""
    path = os.path.join(repo, "doc", "partitioning.md")
    if not os.path.exists(path):
        return ["doc/partitioning.md missing (adaptive-partitioning "
                "operator reference)"]
    text = open(path).read()
    errors: list[str] = []
    settings_src = open(
        os.path.join(repo, "channeld_tpu", "core", "settings.py")
    ).read()
    declared = set(re.findall(r"^    (partition_[a-z0-9_]+):",
                              settings_src, re.M))
    documented = set(re.findall(r"`(partition_[a-z0-9_]+)`", text))
    for name in sorted(declared - documented):
        errors.append(
            f"doc/partitioning.md: knob {name!r} is declared in "
            "core/settings.py but not documented"
        )
    for name in sorted(documented - declared):
        errors.append(
            f"doc/partitioning.md: documents knob {name!r} with no "
            "matching declaration in core/settings.py"
        )
    for rel in ("README.md", "doc/balancer.md", "doc/global_control.md",
                "doc/persistence.md"):
        linked = os.path.join(repo, rel)
        if not os.path.exists(linked) \
                or "partitioning.md" not in open(linked).read():
            errors.append(f"{rel}: no cross-link to doc/partitioning.md")
    return errors


def check_query_engine_doc(repo: str = REPO) -> list[str]:
    """doc/query_engine.md must document every ``queryplane_*``
    operator knob core/settings.py declares (a knob added without doc
    — or documented after removal — is drift), and the docs whose
    planes the standing-query registry rides must cross-link it:
    README, doc/observability.md (the query_plane trace stage),
    doc/partitioning.md (geometry epoch -> query full-resync),
    doc/device_recovery.md (rebuild -> query epoch resync)."""
    path = os.path.join(repo, "doc", "query_engine.md")
    if not os.path.exists(path):
        return ["doc/query_engine.md missing (standing-query plane "
                "operator reference)"]
    text = open(path).read()
    errors: list[str] = []
    settings_src = open(
        os.path.join(repo, "channeld_tpu", "core", "settings.py")
    ).read()
    declared = set(re.findall(r"^    (queryplane_[a-z0-9_]+):",
                              settings_src, re.M))
    documented = set(re.findall(r"`(queryplane_[a-z0-9_]+)`", text))
    for name in sorted(declared - documented):
        errors.append(
            f"doc/query_engine.md: knob {name!r} is declared in "
            "core/settings.py but not documented"
        )
    for name in sorted(documented - declared):
        errors.append(
            f"doc/query_engine.md: documents knob {name!r} with no "
            "matching declaration in core/settings.py"
        )
    for rel in ("README.md", "doc/observability.md",
                "doc/partitioning.md", "doc/device_recovery.md"):
        linked = os.path.join(repo, rel)
        if not os.path.exists(linked) \
                or "query_engine.md" not in open(linked).read():
            errors.append(f"{rel}: no cross-link to doc/query_engine.md")
    return errors


def check_simulation_doc(repo: str = REPO) -> list[str]:
    """doc/simulation.md must document every ``sim_*`` operator knob
    core/settings.py declares, as a row in its knob table (a knob
    added without doc — or documented after removal — is drift). The
    table-row anchor keeps the gate honest: the ``sim_`` prefix is
    shared by the metric family (`sim_pass_ms`, `sim_agents_num`, ...)
    so a bare backtick scan cannot distinguish knob from metric. The
    docs whose planes the population rides must cross-link it: README,
    doc/device_recovery.md (sim columns in the rebuild + sentinel),
    doc/query_engine.md (the danger-zone sensor), doc/chaos.md (the
    ``sim.*`` injection points)."""
    path = os.path.join(repo, "doc", "simulation.md")
    if not os.path.exists(path):
        return ["doc/simulation.md missing (simulation plane operator "
                "reference)"]
    text = open(path).read()
    errors: list[str] = []
    settings_src = open(
        os.path.join(repo, "channeld_tpu", "core", "settings.py")
    ).read()
    declared = set(re.findall(r"^    (sim_[a-z0-9_]+):",
                              settings_src, re.M))
    documented = set(re.findall(r"^\| `(sim_[a-z0-9_]+)` \|",
                                text, re.M))
    for name in sorted(declared - documented):
        errors.append(
            f"doc/simulation.md: knob {name!r} is declared in "
            "core/settings.py but missing from the knob table"
        )
    for name in sorted(documented - declared):
        errors.append(
            f"doc/simulation.md: knob table documents {name!r} with no "
            "matching declaration in core/settings.py"
        )
    for rel in ("README.md", "doc/device_recovery.md",
                "doc/query_engine.md", "doc/chaos.md"):
        linked = os.path.join(repo, rel)
        if not os.path.exists(linked) \
                or "simulation.md" not in open(linked).read():
            errors.append(f"{rel}: no cross-link to doc/simulation.md")
    return errors


def main() -> int:
    errors = (check_artifacts() + check_doc_metrics()
              + check_doc_paths()
              + check_artifact_metrics() + check_concurrency_doc()
              + check_partitioning_doc() + check_query_engine_doc()
              + check_simulation_doc())
    if errors:
        for e in errors:
            print(f"DRIFT: {e}")
        return 1
    print(f"clean: {len(_artifact_paths(REPO))} artifacts, "
          f"{len(registered_metric_names())} metric families")
    return 0


if __name__ == "__main__":
    sys.exit(main())
