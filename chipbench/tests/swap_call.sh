#!/bin/sh
# The hot-swap cell's readings on the chip. The tool ends a command at 45 GiB
# of writes to the machine's disk and a run of 51 s takes 8.8 of them (a swap
# writes 3.7 GB on the two stores; PR 49 lost the sixth run of a call), so a
# call holds FIVE runs, or a traced run, two runs and three controls.
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/tests/swap_call.sh runs <label> <seed>...
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/tests/swap_call.sh trace <seed> [<parent dir>]
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/tests/swap_call.sh controls <seed> [<break>...]
what=$1; shift
out=chiprun_out/sets; mkdir -p $out
run() { # label script seed seconds trace extra...
  label=$1; script=$2; seed=$3; secs=$4; trace=$5; shift 5
  python3 $script "$@" --workload shard-swap --seed $seed --seconds $secs --trace $trace > $out/shard-swap_${label}_$seed.out 2> $out/shard-swap_${label}_$seed.err
  echo "rc=$? $label $seed $(tail -1 $out/shard-swap_${label}_$seed.out | cut -c1-2600)"
  grep "set-up\|warm-up swap\|check:\|a swap, medians\|operations:\|window \|operation .*failed\|compile requests in all" $out/shard-swap_${label}_$seed.out | cut -c13-900
}
case $what in
runs) label=$1; shift; for seed in "$@"; do run $label chipbench/run.py $seed 51 0; done ;;
trace)
  run t1 chipbench/run.py $1 51 1
  if [ -n "$2" ]; then
    start=$(date +%s)
    (cd $2 && timeout 300 python3 chipbench/run.py --workload shard-swap --seed $1 --seconds 51 --trace 0 > ../$out/parent.out 2> ../$out/parent.err; echo "parent rc=$? after $(( $(date +%s) - start )) s"; tail -3 ../$out/parent.err | cut -c1-400; tail -2 ../$out/parent.out | cut -c1-300)
  fi ;;
controls) seed=$1; shift; for how in ${@:-live store version torn}; do run c_$how chipbench/tests/control_swap.py $seed 1 0 --break $how; done ;;
esac
nproc; df -h . | tail -1
