#!/bin/bash
# Tier-1 verify gate — the ONE entry point for local and automated runs.
# Runs the suite as the driver does (CPU, -m 'not slow', six xdist
# workers, one file a worker at a time, 1,470 s cap; ROADMAP.md under
# "Tier-1 verify" has the driver's own line). Exit code is pytest's;
# DOTS_PASSED echoes the per-test pass count the growth driver compares
# against its floor.
#
#   --smoke   the paged-serving slice, one process, for iterating on the
#             continuous batcher / page-table / shared-prefix-attention
#             stack without the whole suite.
cd "$(dirname "$0")/.." || exit 1
# Metrics-drift gate (PR 5): every family the serving stack references
# must be declared in server/metrics.py and documented in the README
# observability table. Stdlib-only, < 1 s.
python scripts/check_metrics.py || exit 1
if [ "$1" = "--smoke" ]; then
  exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_paged_cache.py tests/test_server.py \
    tests/test_shared_prefix_attention.py tests/test_kv_offload.py \
    tests/test_tracing.py tests/test_decode_pipeline.py \
    tests/test_ragged_attention.py tests/test_serve_speculative.py \
    tests/test_flight.py tests/test_decode_rounds.py \
    tests/test_mesh_serving.py tests/test_replica_fleet.py \
    tests/test_adaptive_control.py tests/test_disagg.py \
    tests/test_kv_transfer.py tests/test_multi_model.py \
    tests/test_fleet_control.py tests/test_fleet_observability.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly
fi
set -o pipefail
tmp=${TMPDIR:-/tmp}
rm -f "$tmp/_t1.log" "$tmp/_t1.xml"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml="$tmp/_t1.xml" -p no:randomly 2>&1 | tee "$tmp/_t1.log"
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$tmp/_t1.log" | tr -cd . | wc -c)
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' "$tmp/_t1.log")
exit $rc
