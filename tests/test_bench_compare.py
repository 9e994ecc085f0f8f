"""tools/bench_compare.py tests (ISSUE 8): the perf trajectory's
mechanical regression gate — direction inference, tolerance (global +
per-key), boolean gates, bench-shape flattening, latest-two glob
selection, and exit codes over canned fixtures."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "bench_compare",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "tools", "bench_compare.py"))
bc = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(bc)


OLD = {
    "e2e_rpc_train_samples_per_sec_native": 100000.0,
    "e2e_rpc_classify_p99_ms_native": 10.0,
    "e2e_tracing_overhead_p50_ratio": 1.01,
    "e2e_profiling_overhead_ok": True,
    "collective_wire_mb_per_round": 480.0,
    "e2e_fv_overlap_fraction": 0.8,
    "bench_platform_note": "cpu",   # non-numeric: ignored by flatten
    "e2e_clients": 16,              # no direction: info only
}


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_direction_inference():
    assert bc.direction("e2e_rpc_train_samples_per_sec_native") == "higher"
    assert bc.direction("e2e_fv_overlap_fraction") == "higher"
    assert bc.direction("collective_round_int8_vs_bf16_speedup") == "higher"
    assert bc.direction("e2e_rpc_classify_p99_ms_native") == "lower"
    assert bc.direction("e2e_tracing_overhead_p50_ratio") == "lower"
    assert bc.direction("collective_wire_mb_per_round") == "lower"
    assert bc.direction("collective_round_drift_vs_f32") == "lower"
    assert bc.direction("e2e_profiling_overhead_ok") == "bool"
    assert bc.direction("mix_under_1s_target") == "bool"
    # async mix plane (ISSUE 11): serving-path stall and rounds-behind
    # are down-good; the drift-parity gate is boolean
    assert bc.direction("e2e_train_stall_during_mix_ms") == "lower"
    assert bc.direction("e2e_async_mix_lag_rounds") == "lower"
    assert bc.direction("e2e_async_mix_drift_parity_ok") == "bool"
    assert bc.direction("e2e_clients") is None


def test_direction_inference_autoscale_keys():
    """ISSUE 12 autoscaling plane: recovery wall time and seconds in
    SLO violation gate down-good (a slower control loop is a
    regression), capacity absorbed per serving replica up-good, the
    autoscaled-beats-static verdict is a boolean gate."""
    assert bc.direction("e2e_scaleout_recovery_s") == "lower"
    assert bc.direction("e2e_autoscale_slo_violation_s") == "lower"
    assert bc.direction("e2e_static_slo_violation_s") == "lower"
    assert bc.direction("e2e_capacity_per_replica") == "higher"
    assert bc.direction("e2e_autoscale_beats_static_ok") == "bool"
    # neighbors that must NOT accidentally gate
    assert bc.direction("e2e_autoscale_final_replicas") is None
    assert bc.direction("e2e_fleet_seed") is None


def test_direction_inference_usage_keys():
    """ISSUE 19 usage-attribution plane: the conservation gap gates
    down-good (growth = requests escaping attribution), capacity
    headroom up-good (shrinkage at the same load = costlier replica),
    the overhead verdicts ride the existing _ratio/_ok patterns."""
    assert bc.direction("e2e_usage_attribution_err_frac") == "lower"
    assert bc.direction("e2e_capacity_headroom") == "higher"
    assert bc.direction("e2e_usage_overhead_mean_ratio") == "lower"
    assert bc.direction("e2e_usage_overhead_p50_ratio") == "lower"
    assert bc.direction("e2e_usage_overhead_ok") == "bool"
    assert bc.direction("e2e_usage_attribution_ok") == "bool"
    assert bc.direction("e2e_usage_tenants_distinct_ok") == "bool"
    # neighbors that must NOT accidentally gate
    assert bc.direction("e2e_usage_tenants_seen") is None
    assert bc.direction("e2e_usage_driven_done") is None


def test_usage_keys_gate_over_fixtures():
    """The err_frac/headroom directions drive real verdicts: a grown
    conservation gap and a shrunken headroom each REGRESS; the gap
    shrinking and headroom growing each count as improvements."""
    old = {"e2e_usage_attribution_err_frac": 0.02,
           "e2e_capacity_headroom": 0.9,
           "e2e_usage_overhead_ok": True}
    new = {"e2e_usage_attribution_err_frac": 0.09,
           "e2e_capacity_headroom": 0.4,
           "e2e_usage_overhead_ok": False}
    rows, regs = bc.compare(old, new, tolerance=0.05)
    assert {r["key"] for r in regs} == \
        {"e2e_usage_attribution_err_frac", "e2e_capacity_headroom",
         "e2e_usage_overhead_ok"}
    better = {"e2e_usage_attribution_err_frac": 0.01,
              "e2e_capacity_headroom": 0.95,
              "e2e_usage_overhead_ok": True}
    rows, regs = bc.compare(old, better, tolerance=0.05)
    assert regs == []
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["e2e_usage_attribution_err_frac"] == "improved"
    assert verdicts["e2e_capacity_headroom"] == "improved"


def test_direction_inference_sharded_keys():
    """ISSUE 13 feature-sharding plane: train throughput at d26 gates
    up-good per shard count, classify/KNN query p99 down-good — single-
    AND multi-shard spellings, at both row-count scales."""
    assert bc.direction("sharded_train_samples_per_sec_d26_1shard") \
        == "higher"
    assert bc.direction("sharded_train_samples_per_sec_d26_8shard") \
        == "higher"
    assert bc.direction("sharded_classify_p99_ms_d26_1shard") == "lower"
    assert bc.direction("sharded_classify_p99_ms_d26_8shard") == "lower"
    assert bc.direction("knn_query_p99_ms_rows1e6_1shard") == "lower"
    assert bc.direction("knn_query_p99_ms_rows1e6_8shard") == "lower"
    assert bc.direction("knn_query_p99_ms_rows1e8_1shard") == "lower"
    assert bc.direction("knn_query_p99_ms_rows1e8_8shard") == "lower"
    # neighbors that must NOT accidentally gate
    assert bc.direction("sharded_train_shards") is None
    assert bc.direction("knn_query_rows_rows1e6") is None


def test_direction_inference_quality_keys():
    """ISSUE 17 data-quality plane: PSI drift scores gate down-good,
    prequential/holdout accuracy and ANN recall gate up-good, the
    overhead and tracks-holdout verdicts are boolean gates."""
    assert bc.direction("e2e_drift_baseline_psi") == "lower"
    assert bc.direction("e2e_quality_overhead_mean_ratio") == "lower"
    assert bc.direction("e2e_prequential_accuracy") == "higher"
    assert bc.direction("e2e_holdout_accuracy") == "higher"
    assert bc.direction("e2e_ann_recall") == "higher"
    assert bc.direction("e2e_prequential_tracks_holdout_ok") == "bool"
    assert bc.direction("e2e_quality_overhead_ok") == "bool"
    # the drill verdicts carry "drift" (a bare _LOWER pattern) but the
    # _ok suffix must win: a fired drift alarm in the drill is GOOD
    assert bc.direction("e2e_drift_detected_ok") == "bool"
    assert bc.direction("e2e_drift_slo_fired_ok") == "bool"
    assert bc.direction("e2e_drift_incident_ok") == "bool"
    # neighbors that must NOT accidentally gate
    assert bc.direction("e2e_shift_peak_score") is None
    assert bc.direction("e2e_quality_sample") is None
    assert bc.direction("e2e_recalled_total") is None  # no _recall edge


def test_quality_keys_gate_in_compare():
    old = {"e2e_drift_baseline_psi": 0.03,
           "e2e_prequential_accuracy": 0.90,
           "e2e_ann_recall": 0.80,
           "e2e_prequential_tracks_holdout_ok": True}
    new = {"e2e_drift_baseline_psi": 0.40,     # false alarms leaked: bad
           "e2e_prequential_accuracy": 0.70,   # accuracy fell: bad
           "e2e_ann_recall": 0.99,             # improved
           "e2e_prequential_tracks_holdout_ok": False}  # gate flip
    rows, regs = bc.compare(bc.flatten(old), bc.flatten(new))
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["e2e_drift_baseline_psi"] == "REGRESSED"
    assert verdicts["e2e_prequential_accuracy"] == "REGRESSED"
    assert verdicts["e2e_ann_recall"] == "improved"
    assert verdicts["e2e_prequential_tracks_holdout_ok"] == "REGRESSED"
    assert len(regs) == 3


def test_direction_inference_durable_model_keys():
    """ISSUE 18 durable model plane: coldstart-to-serving wall time and
    killall model loss gate down-good (the loss contract is zero rows
    beyond the diff-chain tail), warm-boot recovery rides the existing
    ``_recovery_s`` pattern, warm-beats-cold is a boolean gate."""
    assert bc.direction("e2e_fleet_coldstart_to_serving_s") == "lower"
    assert bc.direction("e2e_killall_model_loss_rows") == "lower"
    assert bc.direction("e2e_warmboot_recovery_s") == "lower"
    assert bc.direction("e2e_warmboot_beats_cold_ok") == "bool"
    # neighbors that must NOT accidentally gate: raw diagnostics
    assert bc.direction("e2e_killall_tail_window_rows") is None
    assert bc.direction("e2e_warmboot_chain_len") is None
    assert bc.direction("e2e_killall_acked_rows") is None


def test_durable_model_keys_gate_in_compare():
    old = {"e2e_fleet_coldstart_to_serving_s": 9.0,
           "e2e_warmboot_recovery_s": 1.5,
           "e2e_killall_model_loss_rows": 0,
           "e2e_warmboot_beats_cold_ok": True}
    new = {"e2e_fleet_coldstart_to_serving_s": 14.0,  # slower: regression
           "e2e_warmboot_recovery_s": 1.2,            # improved
           "e2e_killall_model_loss_rows": 120,        # durability loss
           "e2e_warmboot_beats_cold_ok": False}       # gate flip
    rows, regs = bc.compare(bc.flatten(old), bc.flatten(new))
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["e2e_fleet_coldstart_to_serving_s"] == "REGRESSED"
    assert verdicts["e2e_killall_model_loss_rows"] == "REGRESSED"
    assert verdicts["e2e_warmboot_beats_cold_ok"] == "REGRESSED"
    assert verdicts["e2e_warmboot_recovery_s"] == "improved"
    assert len(regs) == 3


def test_sharded_keys_gate_in_compare():
    old = {"sharded_train_samples_per_sec_d26_8shard": 50000.0,
           "sharded_classify_p99_ms_d26_8shard": 40.0,
           "knn_query_p99_ms_rows1e8_8shard": 900.0,
           "knn_query_p99_ms_rows1e6_1shard": 8.0}
    new = {"sharded_train_samples_per_sec_d26_8shard": 42000.0,  # regressed
           "sharded_classify_p99_ms_d26_8shard": 36.0,           # improved
           "knn_query_p99_ms_rows1e8_8shard": 1100.0,            # regressed
           "knn_query_p99_ms_rows1e6_1shard": 8.1}               # within tol
    rows, regs = bc.compare(bc.flatten(old), bc.flatten(new))
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["sharded_train_samples_per_sec_d26_8shard"] \
        == "REGRESSED"
    assert verdicts["sharded_classify_p99_ms_d26_8shard"] == "improved"
    assert verdicts["knn_query_p99_ms_rows1e8_8shard"] == "REGRESSED"
    assert verdicts["knn_query_p99_ms_rows1e6_1shard"] == "ok"
    assert len(regs) == 2


def test_autoscale_keys_gate_in_compare(tmp_path):
    old = {"e2e_scaleout_recovery_s": 10.0,
           "e2e_autoscale_slo_violation_s": 12.0,
           "e2e_capacity_per_replica": 1200.0,
           "e2e_autoscale_beats_static_ok": True}
    new = {"e2e_scaleout_recovery_s": 18.0,       # slower: regression
           "e2e_autoscale_slo_violation_s": 11.0,  # improved
           "e2e_capacity_per_replica": 900.0,      # shrank: regression
           "e2e_autoscale_beats_static_ok": False}  # gate flip
    rows, regs = bc.compare(bc.flatten(old), bc.flatten(new))
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["e2e_scaleout_recovery_s"] == "REGRESSED"
    assert verdicts["e2e_capacity_per_replica"] == "REGRESSED"
    assert verdicts["e2e_autoscale_beats_static_ok"] == "REGRESSED"
    assert verdicts["e2e_autoscale_slo_violation_s"] == "improved"
    assert len(regs) == 3


def test_direction_inference_poison_keys():
    """ISSUE 15 model-integrity plane: the poison drill arms a KNOWN
    poisoner, so quarantined counts gate up-good (falling means the
    guard stopped catching it), drift vs the clean twin and rollback
    recovery wall time gate down-good, the load-bearing verdicts are
    boolean gates."""
    assert bc.direction("e2e_poison_quarantined_total") == "higher"
    assert bc.direction("e2e_poison_nan_quarantined") == "higher"
    assert bc.direction("e2e_poison_drift_vs_clean") == "lower"
    assert bc.direction("e2e_rollback_recovery_s") == "lower"
    assert bc.direction("e2e_poison_guard_load_bearing_ok") == "bool"
    assert bc.direction("e2e_poison_zero_nonfinite_applied_ok") == "bool"
    # neighbors that must NOT accidentally gate
    assert bc.direction("e2e_poison_unguarded_corrupted") is None


def test_poison_keys_gate_in_compare():
    old = {"e2e_poison_quarantined_total": 12,
           "e2e_poison_drift_vs_clean": 0.0001,
           "e2e_rollback_recovery_s": 0.1,
           "e2e_poison_guard_load_bearing_ok": True}
    new = {"e2e_poison_quarantined_total": 4,     # guard missing: regression
           "e2e_poison_drift_vs_clean": 0.02,     # drifted: regression
           "e2e_rollback_recovery_s": 0.08,       # improved
           "e2e_poison_guard_load_bearing_ok": False}  # gate flip
    rows, regs = bc.compare(bc.flatten(old), bc.flatten(new))
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["e2e_poison_quarantined_total"] == "REGRESSED"
    assert verdicts["e2e_poison_drift_vs_clean"] == "REGRESSED"
    assert verdicts["e2e_rollback_recovery_s"] == "improved"
    assert verdicts["e2e_poison_guard_load_bearing_ok"] == "REGRESSED"
    assert len(regs) == 3


def test_direction_inference_ann_keys():
    """ISSUE 16 ANN tier: recall@k against the exact scan gates
    up-good (falling recall = wrong neighbors), index build throughput
    rides the existing _per_sec pattern, the IVF query p99 gates
    down-good via _p99_ms like every latency key."""
    assert bc.direction("ann_recall_at_10_rows1e8") == "higher"
    assert bc.direction("ann_recall_at_10_rows1e6") == "higher"
    assert bc.direction("ann_build_rows_per_sec") == "higher"
    assert bc.direction("knn_query_p99_ms_rows1e8_8shard_ivf") == "lower"
    # neighbors that must NOT accidentally gate
    assert bc.direction("ann_nprobe") is None
    assert bc.direction("ann_cells_rows1e8") is None


def test_ann_keys_gate_in_compare():
    old = {"ann_recall_at_10_rows1e8": 0.97,
           "ann_build_rows_per_sec": 500000.0,
           "knn_query_p99_ms_rows1e8_8shard_ivf": 40.0,
           "ann_nprobe": 8}
    new = {"ann_recall_at_10_rows1e8": 0.80,              # recall fell: bad
           "ann_build_rows_per_sec": 650000.0,            # improved
           "knn_query_p99_ms_rows1e8_8shard_ivf": 55.0,   # slower: bad
           "ann_nprobe": 16}                              # info only
    rows, regs = bc.compare(bc.flatten(old), bc.flatten(new))
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["ann_recall_at_10_rows1e8"] == "REGRESSED"
    assert verdicts["ann_build_rows_per_sec"] == "improved"
    assert verdicts["knn_query_p99_ms_rows1e8_8shard_ivf"] == "REGRESSED"
    assert verdicts["ann_nprobe"] == "info"
    assert len(regs) == 2


def test_direction_inference_scaling_keys():
    """ISSUE 9 scaling plane: wire bytes per HOST gate down-good (the
    hierarchical reduce's whole claim), the reduction factor up-good —
    and the factor must win over the _per_host substring it contains."""
    assert bc.direction("collective_wire_bytes_per_host_nproc8_d24") \
        == "lower"
    assert bc.direction(
        "collective_wire_bytes_per_host_nproc8_d24_hier") == "lower"
    assert bc.direction("collective_phase_wire_bytes_per_host_d24") \
        == "lower"
    assert bc.direction("collective_wire_per_host_reduction_nproc8") \
        == "higher"
    assert bc.direction("collective_round_ms_nproc16_d24_hier") == "lower"


def test_nproc16_default_tolerance():
    """The nproc16 wall times swing on scheduler noise (16 gloo
    processes, however few cores): their built-in tolerance is loose,
    the deterministic wire-byte keys keep the tight default, and an
    explicit --key-tolerance still wins."""
    assert bc.default_tolerance_for(
        "collective_round_ms_nproc16_d24", 0.05) == 0.30
    assert bc.default_tolerance_for(
        "collective_round_ms_nproc16_d24_hier", 0.05) == 0.30
    assert bc.default_tolerance_for(
        "collective_round_ms_nproc8_d24", 0.05) == 0.05
    assert bc.default_tolerance_for(
        "collective_wire_bytes_per_host_nproc16_d24", 0.05) == 0.05
    old = {"collective_round_ms_nproc16_d24": 4000.0,
           "collective_wire_bytes_per_host_nproc16_d24": 100663296}
    new = {"collective_round_ms_nproc16_d24": 4800.0,  # +20% < 30%
           "collective_wire_bytes_per_host_nproc16_d24": 100663296}
    _rows, regs = bc.compare(old, new, tolerance=0.05)
    assert regs == []
    new["collective_round_ms_nproc16_d24"] = 5600.0   # +40% > 30%
    _rows, regs = bc.compare(old, new, tolerance=0.05)
    assert [r["key"] for r in regs] == ["collective_round_ms_nproc16_d24"]
    # wire bytes growing is a regression at the tight default: the
    # hierarchical claim IS that this number stays put
    new["collective_round_ms_nproc16_d24"] = 4000.0
    new["collective_wire_bytes_per_host_nproc16_d24"] = 201326592
    _rows, regs = bc.compare(old, new, tolerance=0.05)
    assert [r["key"] for r in regs] == \
        ["collective_wire_bytes_per_host_nproc16_d24"]
    # explicit per-key override still beats the built-in default
    old2 = {"collective_round_ms_nproc16_d24": 4000.0}
    new2 = {"collective_round_ms_nproc16_d24": 4800.0}
    _rows, regs = bc.compare(
        old2, new2, tolerance=0.05,
        key_tolerance={"collective_round_ms_nproc16_d24": 0.10})
    assert len(regs) == 1


def test_flatten_collapses_round_envelopes():
    envelope = {"n": 5, "rc": 0, "tail": "…",
                "parsed": {"metric": "x", "value": 2.0,
                           "extra": {"e2e_a_samples_per_sec": 10.0,
                                     "nested": {"k_ms": 1.0}}}}
    flat = bc.flatten(envelope)
    # parsed/extra collapse WITHOUT a prefix; other dicts keep one
    assert flat["e2e_a_samples_per_sec"] == 10.0
    assert flat["value"] == 2.0
    assert flat["nested.k_ms"] == 1.0
    assert "tail" not in flat
    # flat maps (bench_serving output) pass through
    assert bc.flatten({"a_ms": 1.5})["a_ms"] == 1.5


def test_regressions_flagged_beyond_tolerance():
    new = dict(OLD)
    new["e2e_rpc_train_samples_per_sec_native"] = 80000.0   # -20%: bad
    new["e2e_rpc_classify_p99_ms_native"] = 13.0            # +30%: bad
    new["e2e_profiling_overhead_ok"] = False                # flip: bad
    new["collective_wire_mb_per_round"] = 120.0             # -75%: good
    rows, regs = bc.compare(OLD, new, tolerance=0.05)
    bad = {r["key"] for r in regs}
    assert bad == {"e2e_rpc_train_samples_per_sec_native",
                   "e2e_rpc_classify_p99_ms_native",
                   "e2e_profiling_overhead_ok"}
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["collective_wire_mb_per_round"] == "improved"
    assert verdicts["e2e_clients"] == "info"


def test_within_tolerance_is_clean():
    new = dict(OLD)
    new["e2e_rpc_train_samples_per_sec_native"] = 96500.0   # -3.5% < 5%
    new["e2e_rpc_classify_p99_ms_native"] = 10.4            # +4%  < 5%
    _rows, regs = bc.compare(OLD, new, tolerance=0.05)
    assert regs == []


def test_per_key_tolerance_override():
    new = dict(OLD)
    new["e2e_rpc_classify_p99_ms_native"] = 14.0            # +40%
    _r, regs = bc.compare(OLD, new, tolerance=0.05)
    assert len(regs) == 1
    _r, regs = bc.compare(
        OLD, new, tolerance=0.05,
        key_tolerance={"e2e_rpc_classify_p99_ms_native": 0.5})
    assert regs == []


def test_added_removed_keys_never_gate():
    new = dict(OLD)
    del new["collective_wire_mb_per_round"]
    new["brand_new_ms"] = 5.0
    rows, regs = bc.compare(OLD, new)
    assert regs == []
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["collective_wire_mb_per_round"] == "removed"
    assert verdicts["brand_new_ms"] == "added"


def test_main_exit_codes_over_fixtures(tmp_path, capsys):
    old_p = _write(tmp_path, "BENCH_r01.json", OLD)
    good = dict(OLD)
    good["e2e_rpc_train_samples_per_sec_native"] = 120000.0
    good_p = _write(tmp_path, "BENCH_r02.json", good)
    bad = dict(OLD)
    bad["e2e_rpc_train_samples_per_sec_native"] = 50000.0
    bad_p = _write(tmp_path, "BENCH_r03.json", bad)

    assert bc.main([old_p, good_p]) == 0
    out = capsys.readouterr().out
    assert "improved" in out and "0 regressed" in out
    assert bc.main([old_p, bad_p]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    # round envelopes flatten the same way end to end
    env_old = _write(tmp_path, "env_old.json",
                     {"parsed": {"extra": OLD}, "rc": 0})
    assert bc.main([env_old, bad_p]) == 1
    capsys.readouterr()
    # usage errors
    assert bc.main([]) == 2
    assert bc.main([old_p, "/nonexistent.json"]) == 2
    assert bc.main([old_p, good_p, "--key-tolerance", "nonsense"]) == 2


def test_glob_picks_latest_two(tmp_path, capsys):
    _write(tmp_path, "BENCH_r01.json", OLD)
    mid = dict(OLD)
    mid["e2e_rpc_train_samples_per_sec_native"] = 50000.0
    _write(tmp_path, "BENCH_r02.json", mid)
    new = dict(mid)
    new["e2e_rpc_train_samples_per_sec_native"] = 51000.0
    _write(tmp_path, "BENCH_r03.json", new)
    # latest two = r02 -> r03 (within tolerance); the r01 drop is not
    # in the window
    assert bc.main(["--glob", str(tmp_path / "BENCH_r*.json")]) == 0
    out = capsys.readouterr().out
    assert "BENCH_r02.json" in out and "BENCH_r03.json" in out
    with pytest.raises(ValueError):
        bc.pick_latest_two(str(tmp_path / "nope*.json"))


def test_direction_inference_tune_keys():
    """ISSUE 20 self-tuning plane: regret (tuned-vs-hand-tuned round
    time) rides the _ratio pattern; the rounds-to-converge count is its
    own down-good pattern (growth = the search got slower); the
    observe-mode A/B overhead rides _ratio too."""
    assert bc.direction("e2e_tune_regret_ratio") == "lower"
    assert bc.direction("e2e_tune_converge_rounds") == "lower"
    assert bc.direction("e2e_tune_observe_overhead_ratio") == "lower"
    # neighbors that must NOT accidentally gate
    assert bc.direction("e2e_tune_rounds_total") is None
    assert bc.direction("e2e_tune_plans_scored") is None


def test_tune_keys_gate_over_fixtures():
    """The regret/converge directions drive real verdicts: regret
    drifting up or the search needing more rounds each REGRESS; both
    shrinking count as improvements."""
    old = {"e2e_tune_regret_ratio": 1.10,
           "e2e_tune_converge_rounds": 8}
    worse = {"e2e_tune_regret_ratio": 1.40,
             "e2e_tune_converge_rounds": 14}
    rows, regs = bc.compare(old, worse, tolerance=0.05)
    assert {r["key"] for r in regs} == \
        {"e2e_tune_regret_ratio", "e2e_tune_converge_rounds"}
    better = {"e2e_tune_regret_ratio": 1.02,
              "e2e_tune_converge_rounds": 5}
    rows, regs = bc.compare(old, better, tolerance=0.05)
    assert regs == []
    verdicts = {r["key"]: r["verdict"] for r in rows}
    assert verdicts["e2e_tune_regret_ratio"] == "improved"
    assert verdicts["e2e_tune_converge_rounds"] == "improved"
