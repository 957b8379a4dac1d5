#!/usr/bin/env bash
# The repo lint/type gate, one command locally == the CI `lint` job:
#   ruff      — pycodestyle/pyflakes/bugbear subset (pyproject.toml),
#               plus import sorting scoped to the analysis package;
#   mypy      — scoped strictness (config/logging/service/scheduler strict,
#               rest permissive; see [tool.mypy] in pyproject.toml);
#   graftlint — TPU-correctness rules GL001–GL024 (per-file TPU rules
#               plus project-wide concurrency analysis) against the committed
#               baseline (gofr_tpu/analysis; docs/advanced-guide/
#               static-analysis.md).
#
# ruff/mypy are optional locally (skipped with a warning when not
# installed); graftlint ships with the repo and always runs.
set -uo pipefail
cd "$(dirname "$0")/.."

failed=0

if command -v ruff >/dev/null 2>&1; then
  echo "== ruff =="
  ruff check gofr_tpu/ tests/ examples/ chip_smoke.py __graft_entry__.py || failed=1
  ruff check --select I gofr_tpu/analysis tests/test_graftlint.py || failed=1
else
  echo "== ruff == SKIPPED (not installed; pip install ruff)"
fi

if command -v mypy >/dev/null 2>&1; then
  echo "== mypy (scoped) =="
  mypy gofr_tpu/analysis gofr_tpu/config gofr_tpu/logging \
    gofr_tpu/metrics gofr_tpu/tracing gofr_tpu/faults \
    gofr_tpu/ops/kv_cache.py \
    gofr_tpu/service \
    gofr_tpu/serving/types.py gofr_tpu/serving/lifecycle.py \
    gofr_tpu/serving/engine.py gofr_tpu/serving/backend.py \
    gofr_tpu/serving/batcher.py gofr_tpu/serving/brownout.py \
    gofr_tpu/serving/control_plane.py \
    gofr_tpu/serving/supervisor.py \
    gofr_tpu/serving/watchdog.py gofr_tpu/serving/scheduler.py \
    gofr_tpu/serving/observability.py gofr_tpu/serving/radix_cache.py \
    gofr_tpu/serving/prefix_cache.py gofr_tpu/serving/programs.py \
    gofr_tpu/serving/device_telemetry.py \
    gofr_tpu/serving/loop_profiler.py \
    gofr_tpu/serving/profiler_capture.py \
    gofr_tpu/serving/tenant_ledger.py gofr_tpu/serving/slo.py \
    gofr_tpu/serving/openai_compat.py \
    gofr_tpu/pubsub gofr_tpu/serving/async_serving.py || failed=1
else
  echo "== mypy == SKIPPED (not installed; pip install mypy)"
fi

echo "== graftlint =="
python -m gofr_tpu.analysis gofr_tpu/ --check-baseline || failed=1

exit "$failed"
