#!/bin/sh
# The builder's chip runs of a new cell, in one call, from the tree it is run
# in (unpack `git archive $(git write-tree)` into .smoke_tree and run there to
# prove the committed files are enough): six untraced runs a seed each through
# benchmark/study.py and its table, two traced runs, then the parent's tree
# with this tree's benchmark laid over it, as the driver tries a new cell
# there: the new cell (it must fail at once, not hang) and one old cell traced.
#
#   mkdir -p .archive_tree/parent && git archive <parent> | tar -x -C .archive_tree/parent
#   chiprun --timeout 3000 -- sh scripts/chip_hostfed.sh c5-hostfed c5-sumfirst
#
# Result lines: chiprun_out/<cell>-lines.jsonl; the study: chiprun_out/study/.
set -u
cell=$1
old=$2
seed=${SEED:-2400003400}
here=$(pwd)
mkdir -p chiprun_out
python benchmark/study.py run --workload "$cell" --sets A --runs 6 --seconds 30 \
  --out chiprun_out/study --first-seed "$seed"
python benchmark/study.py collect chiprun_out/study --windows 30 | tee "chiprun_out/study-$cell.txt"
cp "benchmark/out/spread-$cell.json" chiprun_out/ 2>/dev/null
for n in 7 8; do
  python benchmark/run.py --workload "$cell" --seed $((seed + n)) --seconds 30 --trace 1 \
    | tee -a "chiprun_out/$cell-lines.jsonl"
  echo "[chip_hostfed] traced seed=$((seed + n)) rc=$?" >&2
done
cp benchmark/out/rounds-"$cell"-*-trace1.json chiprun_out/ 2>/dev/null
# the parent with this tree's benchmark over it
over=$(mktemp -d)
cp -r .archive_tree/parent/. "$over"
cp BENCHMARK.json "$over"/ && cp -r benchmark/. "$over"/benchmark/ && cp -r tests/benchmark/. "$over"/tests/benchmark/
start=$(date +%s)
(cd "$over" && python benchmark/run.py --workload "$cell" --seed $((seed + 9)) --seconds 30 --trace 0) \
  > "chiprun_out/parent-$cell.out" 2> "chiprun_out/parent-$cell.err"
echo "[chip_hostfed] parent, $cell: rc=$? after $(( $(date +%s) - start )) s" | tee -a "chiprun_out/parent-$cell.out" >&2
(cd "$over" && python benchmark/run.py --workload "$old" --seed $((seed + 10)) --seconds 30 --trace 1) \
  | tee -a "chiprun_out/parent-$old-traced.jsonl"
echo "[chip_hostfed] parent, $old traced: rc=$?" >&2
cd "$here"
