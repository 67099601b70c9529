#!/bin/bash
# Two sets of 6 runs of one cell (the same seeds in both), then one traced
# run; every last line goes to chiprun_out/sets.<cell>.jsonl.
#   bash benchmark/tools/measure_sets.sh <cell> <seconds> [first_seed]
cell=$1; seconds=$2; first=${3:-2147485000}
mkdir -p chiprun_out; out=chiprun_out/sets.$cell.jsonl; : > $out
run() { # set seed trace
  SECONDS=0
  python benchmark/run.py --workload $cell --seed $2 --seconds $seconds --trace $3 > chiprun_out/$cell.$1.$2.log 2> chiprun_out/$cell.$1.$2.err
  rc=$?
  last=$(tail -1 chiprun_out/$cell.$1.$2.log)
  echo "{\"set\": \"$1\", \"seed\": $2, \"rc\": $rc, \"wall_s\": $SECONDS, \"line\": $last}" >> $out
  echo "== $cell set $1 seed $2 rc=$rc wall ${SECONDS}s $(echo $last | cut -c1-420)"
  if [ $rc -ne 0 ]; then grep -v Warn chiprun_out/$cell.$1.$2.err | tail -5 | cut -c1-300; fi
}
for set in A B; do
  for k in 1 2 3 4 5 6; do run $set $((first + 1009 * k)) 0; done
done
run T $((first + 1009 * 7)) 1
grep '"check"' chiprun_out/$cell.A.*.log chiprun_out/$cell.B.*.log | grep -c '"correct": true'
du -sh benchmark/.work/jax_cache/$cell
