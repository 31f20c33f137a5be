#!/bin/sh
# The save-and-resume cell's readings on the chip. Both hosts' stores lie on
# the memory-backed scratch, so a run writes little to the machine's disk (the
# logs, the compile cache: df reads the same before and after a call of four
# runs; the machine has no /proc/diskstats); a run of 51 s lasts 137 s.
#   chiprun --chips 1 --timeout 2400 -- sh chipbench/tests/save_call.sh runs <label> <seed>...
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/tests/save_call.sh trace <seed> [<parent dir>]
#   chiprun --chips 1 --timeout 1500 -- sh chipbench/tests/save_call.sh controls <seed> [<break>...]
what=$1; shift
out=chiprun_out/sets; mkdir -p $out
run() { # label script seed seconds trace extra...
  label=$1; script=$2; seed=$3; secs=$4; trace=$5; shift 5
  start=$(date +%s)
  python3 $script "$@" --workload ckpt-save-resume --seed $seed --seconds $secs --trace $trace > $out/ckpt-save-resume_${label}_$seed.out 2> $out/ckpt-save-resume_${label}_$seed.err
  echo "rc=$? $label $seed wall $(( $(date +%s) - start )) s $(tail -1 $out/ckpt-save-resume_${label}_$seed.out | cut -c1-3000)"
  grep "set-up\|warm-up operation\|check:\|an operation, medians\|operations:\|own work\|window \|operation .*failed\|control\|compile requests in all" $out/ckpt-save-resume_${label}_$seed.out | cut -c13-1200
}
case $what in
runs) label=$1; shift; for seed in "$@"; do run $label chipbench/run.py $seed 51 0; done ;;
trace)
  run t1 chipbench/run.py $1 51 1
  if [ -n "$2" ]; then
    start=$(date +%s)
    (cd $2 && timeout 300 python3 chipbench/run.py --workload ckpt-save-resume --seed $1 --seconds 51 --trace 0 > ../$out/parent.out 2> ../$out/parent.err; echo "parent rc=$? after $(( $(date +%s) - start )) s"; tail -3 ../$out/parent.err | cut -c1-400; tail -2 ../$out/parent.out | cut -c1-300)
  fi ;;
controls) seed=$1; shift; for how in ${@:-flip replica}; do run c_$how chipbench/tests/control_save.py $seed 1 0 --break $how; done ;;
esac
nproc; df -h /dev/shm . | tail -2
