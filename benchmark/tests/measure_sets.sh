#!/bin/bash
# Two sets of N runs of one cell with the same seeds in both, as the bounds are
# set from (PERF.md section 2), then one traced run.  Run through the chip tool:
#   chiprun -- bash benchmark/tests/measure_sets.sh <workload> [N] [seconds]
W=$1; N=${2:-6}; S=${3:-20}
mkdir -p chiprun_out
for set in 1 2; do for i in $(seq 1 $N); do
  python3 benchmark/run.py --workload $W --seed $((3000000000+i)) --seconds $S --trace 0 2>/dev/null \
    | grep -a "^setup\|^{\|FAIL" | sed "s/^/set$set run$i /" | tee -a chiprun_out/sets_$W.txt | cut -c1-600
done; done
python3 benchmark/run.py --workload $W --seed 3000000007 --seconds $S --trace 1 2>/dev/null \
  | grep -a "^{\|FAIL" | sed "s/^/trace run1 /" | tee -a chiprun_out/sets_$W.txt | cut -c1-2500
