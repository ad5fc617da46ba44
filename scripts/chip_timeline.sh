#!/bin/sh
# The program's timeline read on the chip, in one call (PR 37):
#
#   mkdir -p .archive_tree/parent && git archive <parent> | tar -x -C .archive_tree/parent
#   chiprun --timeout 1800 -- sh scripts/chip_timeline.sh
#
# 1. scripts/span_cost.py on the parent's telemetry and on this tree's: one span,
#    one call of the JAX-event listener, one snapshot of a full ring.
# 2. c5-hostfed, untraced, 30 s, under scripts/setup_events.py: the feed's puts,
#    waits by bound and own seconds a round, with no profiler on the link.
# 3. c5-masked and c5-sumfirst, a cold run (5 s) and a warm one (30 s) each,
#    every run under BOTH readers at once: the parent's scripts/setup_events.py
#    (jax.monitoring listeners of its own, registered from outside) runs a
#    three-line run.py that runs this tree's scripts/setup_events.py, which runs
#    benchmark/run.py. So the parent's event table (setup-events-<tag>.json) and
#    the program's own counters and ring (timeline-<tag>.json) are of the same run.
# Each run's record (rounds-*.json) is copied beside them.
set -u
here=$(pwd)
parent=$here/.archive_tree/parent
seed=${SEED:-2700000370}
mkdir -p chiprun_out

for side in parent change; do
  case $side in parent) tree=$parent ;; *) tree=$here ;; esac
  PYTHONPATH=$tree python scripts/span_cost.py > "chiprun_out/span-cost-$side.json"
  echo "[chip_timeline] span_cost $side rc=$?" >&2
done

JAX_COMPILATION_CACHE_DIR=$(mktemp -d) python scripts/setup_events.py --tag c5-hostfed -- \
  --workload c5-hostfed --seed "$seed" --seconds 30 --trace 0 > chiprun_out/line-c5-hostfed.json
echo "[chip_timeline] c5-hostfed rc=$?" >&2

chain=$(mktemp -d)
mkdir -p "$chain/benchmark"
cat > "$chain/benchmark/run.py" <<SHIM
import os, runpy, sys
sys.path.insert(0, "$here/scripts")
sys.argv = ["setup_events.py", "--tree", "$here", "--tag", os.environ["TIMELINE_TAG"], "--", *sys.argv[1:]]
runpy.run_path("$here/scripts/setup_events.py", run_name="__main__")
SHIM
n=0
for cell in c5-masked c5-sumfirst; do
  cache=$(mktemp -d)
  for phase in cold:5 warm:30; do
    n=$((n + 1))
    tag=$cell-${phase%:*}
    TIMELINE_TAG=$tag JAX_COMPILATION_CACHE_DIR=$cache python "$parent/scripts/setup_events.py" \
      --tree "$chain" --tag "$tag" -- \
      --workload "$cell" --seed $((seed + n)) --seconds "${phase#*:}" --trace 0 > "chiprun_out/line-$tag.json"
    echo "[chip_timeline] $tag rc=$?" >&2
  done
done
cp "$parent"/chiprun_out/setup-events-*.json benchmark/out/rounds-*.json chiprun_out/
