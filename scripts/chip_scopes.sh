#!/bin/sh
# The per-round split of PERF.md §5, on the chip: benchmark/scopes.py in each
# cell named, every process from an empty compile cache, traced 12 s windows,
# a seed a cell. One JSON line a cell, kept in chiprun_out/scopes-<cell>.json.
#
#   chiprun --timeout 1500 -- sh scripts/chip_scopes.sh c5-sumfirst c4-participant c4-sumfirst
#   chiprun --chips 4 --timeout 900 -- sh scripts/chip_scopes.sh c5-sumfirst-x4
set -u
mkdir -p chiprun_out
seed=${SEED:-2400000100}
worst=0
for cell in "$@"; do
  seed=$((seed + 1))
  JAX_COMPILATION_CACHE_DIR=$(mktemp -d) python benchmark/scopes.py \
    --workload "$cell" --seed "$seed" --seconds "${SECONDS_TRACED:-12}" \
    > "chiprun_out/scopes-$cell.json"
  rc=$?
  cat "chiprun_out/scopes-$cell.json"
  echo "[chip_scopes] $cell seed=$seed rc=$rc" >&2
  [ "$rc" -gt "$worst" ] && worst=$rc
done
exit "$worst"
