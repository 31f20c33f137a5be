#!/bin/sh
# One run of a cell per seed, end-to-end metrics (--trace 0); the last line of
# each run is echoed, everything else goes under chiprun_out/sets/.
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/tests/sets.sh <cell> <seconds> <label> <seed>...
cell=$1; secs=$2; label=$3; shift 3
mkdir -p chiprun_out/sets
for seed in "$@"; do
  out=chiprun_out/sets/${cell}_${label}_${seed}
  python3 chipbench/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > $out.out 2> $out.err
  echo "rc=$? $cell $label $seed $(tail -1 $out.out | cut -c1-420)"
done
