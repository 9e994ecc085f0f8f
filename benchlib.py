"""jax-free bench plumbing: the durable-then-compact output path and the
round numbering. Lives in its own module so the unit tests exercise it
without importing the device stack.
"""

import json
import os
import sys

#: compact-summary key budget. The driver keeps only the LAST ~2000 chars
#: of stdout; round 4's headline keys printed first and were cut off the
#: artifact of record. The summary stays under this so
#: metric+platform+headline always survive the window.
SUMMARY_BYTES = 1800

#: extra-keys priority for the compact summary, most critical first: the
#: platform label and headline context, then the chip/d24 axis, then the
#: serving plane, then mix. Everything else rides in BENCH_FULL only.
SUMMARY_EXACT = (
    "bench_platform",
    "bench_device_kind",
    "bench_device_count",
    "failed_phases",
    "full_write_error",
    "baseline_impl",
    "baseline_samples_per_sec",
    "tpu_d2^24_samples_per_sec",
    "baseline_cpp_d2^24_samples_per_sec",
    "tpu_d2^24_error",
    "e2e_rpc_train_samples_per_sec_native",
    "e2e_rpc_train_samples_per_sec_python",
    "e2e_transport_ratio_native_vs_python",
    "e2e_proxy_vs_direct",
    "e2e_rpc_train_samples_per_sec_combo",
    "e2e_rpc_train_samples_per_sec_combo_python",
    "e2e_combo_native_vs_python",
    "e2e_combo_features_per_datum",
    "e2e_rpc_train_samples_per_sec_text_filter",
    "e2e_fast_path_fraction_text_filter",
    "e2e_rpc_classify_samples_per_sec_native",
    "e2e_classify_dispatches_per_sec_native",
    "e2e_classify_avg_coalesced_batch_native",
    "e2e_schema_flush_fraction_native",
    "e2e_schema_query_flush_fraction_native",
    "e2e_mixed_train_classify_samples_per_sec",
    "e2e_mixed_train_samples_per_sec",
    "e2e_mixed_classify_samples_per_sec",
    "mix_round_worst_ms",
    "mix_under_1s_target",
    "collective_round_ms_nproc4_d24",
    "collective_round_ms_nproc4_d24_bf16",
    "collective_round_d24_platform",
)
#: prefix fallback order for keys not named above
SUMMARY_PREFIX = ("e2e_", "mix_", "collective_", "chip_", "cpu_", "tpu_")


def summarize(payload: dict, full_name: str) -> dict:
    """The <=SUMMARY_BYTES digest of a full bench payload.

    Keys enter by SUMMARY_EXACT order, then SUMMARY_PREFIX groups, then
    the rest, until the serialized summary would exceed the budget;
    "keys_dropped" counts what only BENCH_FULL carries."""
    head = {k: payload[k] for k in ("metric", "value", "unit", "vs_baseline")}
    head["full"] = full_name
    extra = payload.get("extra", {})
    ordered = [k for k in SUMMARY_EXACT if k in extra]
    seen = set(ordered)
    for pref in SUMMARY_PREFIX:
        ordered += sorted(k for k in extra
                          if k.startswith(pref) and k not in seen)
        seen.update(ordered)
    ordered += sorted(k for k in extra if k not in seen)
    out = dict(head)
    out["extra"] = {}
    dropped = 0
    for k in ordered:
        trial = dict(out)
        trial["extra"] = {**out["extra"], k: extra[k]}
        # size against the WORST-CASE dropped count so the final patch
        # below can only shrink the line, never push it past the budget
        trial["keys_dropped"] = len(extra)
        if len(json.dumps(trial)) > SUMMARY_BYTES:
            dropped += 1
            continue
        out = trial
    out["keys_dropped"] = dropped
    return out


def emit(payload: dict) -> None:
    """Durable-then-compact output.

    The FULL payload goes to BENCH_FULL_r{N}.json in the repo (the
    durable artifact, like linear_mixer.cpp:553-558's per-round log) and
    to stderr for interactive runs; stdout gets exactly one compact JSON
    line, printed LAST, sized to survive a last-2000-chars window."""
    here = os.path.dirname(os.path.abspath(__file__))
    stem = f"BENCH_FULL_r{current_round():02d}"
    full_name = f"{stem}.json"
    # a capture is never clobbered: a second run in the same round
    # diverts to a numbered sibling
    n = 2
    while os.path.exists(os.path.join(here, full_name)):
        full_name = f"{stem}_{n}.json"
        n += 1
    path = os.path.join(here, full_name)
    try:
        with open(path, "w") as f:
            f.write(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    except OSError as e:
        payload.setdefault("extra", {})["full_write_error"] = repr(e)[:120]
        full_name = None  # the pointer must not name a file that isn't there
    # serialize AFTER any error-key mutation so stderr carries it too
    print(json.dumps(payload, indent=1, sort_keys=True), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(summarize(payload, full_name)))
    sys.stdout.flush()


def current_round() -> int:
    """The round now in progress, from the driver's BENCH_r{N}.json trail.

    The driver writes BENCH_r{N}.json at the END of round N, so the
    in-progress round is max(N)+1. JUBATUS_BENCH_ROUND overrides (e.g. a
    re-run inside an already-captured round). Non-numeric matches are
    skipped, never fatal — bench.emit() must not crash at the end of a
    run."""
    import glob
    import re

    env = os.environ.get("JUBATUS_BENCH_ROUND")
    if env and env.isdigit():
        return int(env)
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = []
    for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", os.path.basename(p))
        if m:
            rounds.append(int(m.group(1)))
    return (max(rounds) + 1) if rounds else 1
