#!/bin/sh
# The feed cell's whole reading on the chip, one call: a run, a traced run, the
# three controls, five more seeds, and the cell tried on a parent checkout.
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/tests/feed_call.sh <parent dir or -> <seed>...   (8 seeds)
parent=$1; shift
out=chiprun_out/feed; mkdir -p $out
run() { # label script seed seconds trace extra...
  label=$1; script=$2; seed=$3; secs=$4; trace=$5; shift 5
  python3 $script "$@" --workload feed-records --seed $seed --seconds $secs --trace $trace > $out/${label}_$seed.out 2> $out/${label}_$seed.err
  echo "rc=$? $label $seed $(tail -1 $out/${label}_$seed.out | cut -c1-2500)"
  grep "set-up:\|check:\|a batch, medians\|operations:\|outran\|window \|compile requests in all" $out/${label}_$seed.out | cut -c13-640
}
run t0 chipbench/run.py $1 51 0
tail -1 $out/t0_$1.out | grep -q '"correct": true' || { tail -30 $out/t0_$1.err; tail -30 $out/t0_$1.out | cut -c1-400; exit 1; }
run t1 chipbench/run.py $2 51 1
for how in flip swap numpy; do run c_$how chipbench/tests/control_feed.py $3 1 0 --break $how; done
shift 3
for seed in "$@"; do run t0 chipbench/run.py $seed 51 0; done
if [ "$parent" != "-" ]; then
  start=$(date +%s)
  (cd $parent && timeout 300 python3 chipbench/run.py --workload feed-records --seed $1 --seconds 51 --trace 0 > ../$out/parent.out 2> ../$out/parent.err; echo "parent rc=$? after $(( $(date +%s) - start )) s"; tail -4 ../$out/parent.err | cut -c1-400; tail -2 ../$out/parent.out | cut -c1-300)
fi
ulimit -n; nproc; df -h . | tail -1
