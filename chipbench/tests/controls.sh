#!/bin/sh
# The control on the chip: a cell's run with the landing broken underneath
# (tests/control.py); correct must come out false.
#   chiprun --chips 1 -- sh chipbench/tests/controls.sh <cell> <seconds> <flip|zero> <seed>...
cell=$1; secs=$2; how=$3; shift 3
mkdir -p chiprun_out/controls
for seed in "$@"; do
  out=chiprun_out/controls/${cell}_${how}_${seed}
  python3 chipbench/tests/control.py --break $how --workload $cell --seed $seed --seconds $secs --trace 0 > $out.out 2> $out.err
  echo "rc=$? control $how $cell $seed $(grep 'check: pieces\|check: fetched' $out.out | cut -c18-130 | tr '\n' '|') $(tail -1 $out.out | cut -c1-60)"
done
