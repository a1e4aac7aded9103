#!/bin/bash
# Runs benchmark cells one after the other on the chip machine and keeps each
# run's last line and log under chiprun_out/<tag>/.
#   scripts/chip_cell_runs.sh <tag> <cell> <trace 0|1> <seed> [<seed> ...]
# Set ROOT to run another checkout (a parent unpacked under .chip_archive/).
tag=$1; cell=$2; trace=$3; shift 3
root=${ROOT:-.}
out=$PWD/chiprun_out/$tag
mkdir -p "$out"
for seed in "$@"; do
  extra=""
  [ "$trace" = 1 ] && [ -n "$KEEP_TRACE" ] && extra="--keep-trace $out/trace_$seed"
  (cd "$root" && python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
      --seconds 45 --trace "$trace" $extra) > "$out/$cell.$seed.t$trace.log" 2> "$out/$cell.$seed.t$trace.err"
  echo "rc=$? cell=$cell seed=$seed trace=$trace root=$root"
  tail -n 1 "$out/$cell.$seed.t$trace.log" | cut -c1-3000
done
