#!/bin/sh
# The readings of the layer "event loop" on the chip, one call each:
#   chiprun --chips 1 --timeout 1800 -- sh chipbench/tests/loop_call.sh traced <dir> <seed> <cell>...
#       a traced run of each cell from the checkout <dir> (. or a parent's copy
#       under the benchmark's files), seeds <seed>, <seed>+1, ...: the result
#       line, the log's "loop:" lines and the timelines of its operations
#   chiprun --chips 1 --timeout 3000 -- sh chipbench/tests/loop_call.sh onoff <dir> <cell> <seed>...
#       what the account costs: the cell with --trace 0 from this checkout and
#       from <dir> (made here: a copy of this checkout whose ProfConfig.enabled
#       defaults to False; name one that .gitignore lists), by turns, each
#       seed on both sides, the side that goes first alternating
mode=$1; dir=$2; shift 2
out=$(pwd)/chiprun_out/loop; mkdir -p $out
run() { # label dir cell seed trace
  (cd $2 && python3 chipbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $out/$1_$3_$4.out 2> $out/$1_$3_$4.err)
  echo "rc=$? $1 $3 $4 $(tail -1 $out/$1_$3_$4.out | cut -c1-6000)"
  grep "operations:\|window \|loop:" $out/$1_$3_$4.out | cut -c13-1200
}
case $mode in
traced)
  seed=$1; shift
  for cell in "$@"; do
    run traced $dir $cell $seed 1
    grep "operation [0-9]*: " $out/traced_${cell}_$seed.out | head -4 | cut -c13-900
    # A reader that raises or reads nothing would do so in every cell: stop.
    [ "$dir" != . ] || tail -1 $out/traced_${cell}_$seed.out | grep -q loop_busy_ms || {
      tail -30 $out/traced_${cell}_$seed.err | cut -c1-400; exit 1; }
    seed=$((seed + 1))
  done;;
onoff)
  if [ ! -d $dir ]; then
    mkdir $dir
    tar -cf - --exclude=$dir --exclude=chiprun_out --exclude='.archive_check*' \
      --exclude=.jax_cache --exclude=.run . | tar -xf - -C $dir
    sed -i 's/^    enabled: bool = True/    enabled: bool = False/' $dir/dragonfly2_tpu/pkg/prof.py
    grep -n "^    enabled: bool" $dir/dragonfly2_tpu/pkg/prof.py
  fi
  cell=$1; shift; turn=0
  for seed in "$@"; do
    if [ $((turn % 2)) = 0 ]; then run on . $cell $seed 0; run off $dir $cell $seed 0
    else run off $dir $cell $seed 0; run on . $cell $seed 0; fi
    turn=$((turn + 1))
  done;;
esac
nproc
