#!/bin/sh
# Parent against change in one cell, on the same chips, in one call: parent,
# change, change, parent; each side keeps a compile cache of its own (the
# cache key strips debug info, so a shared one would hand the parent's
# executables to the change); the two sides of a pair share a seed.
#
#   mkdir -p .archive_tree/parent && git archive <parent> | tar -x -C .archive_tree/parent
#   chiprun --timeout 1500 -- sh scripts/chip_ab.sh c4-sumfirst 30
#
# (The archive is made before the call: the chip's machine has no .git.)
# Result lines: chiprun_out/ab-<cell>.jsonl, each tagged with its side.
set -u
cell=$1
seconds=${2:-30}
seed=${SEED:-2400000200}
here=$(pwd)
mkdir -p chiprun_out
for side in parent change; do eval "cache_$side=\$(mktemp -d)"; done
run() {  # side seed
  case $1 in parent) tree=$here/.archive_tree/parent; cache=$cache_parent ;; *) tree=$here; cache=$cache_change ;; esac
  line=$(cd "$tree" && JAX_COMPILATION_CACHE_DIR=$cache python benchmark/run.py \
    --workload "$cell" --seed "$2" --seconds "$seconds" --trace 0)
  echo "[chip_ab] $1 seed=$2 rc=$?" >&2
  echo "{\"side\": \"$1\", \"line\": $line}" | tee -a "chiprun_out/ab-$cell.jsonl"
}
run parent $((seed + 1)); run change $((seed + 1)); run change $((seed + 2)); run parent $((seed + 2))
