#!/bin/bash
# Two sets of N runs of one cell, EVERY run on a seed of its own (the driver
# draws new seeds for every check; measure_sets.sh gives both sets the same
# seeds to take the seed out of the spread), then one traced run.  Keeps each
# run's compare lines beside its setup and result lines.  Run through the chip tool:
#   chiprun -- bash benchmark/tests/measure_sets_fresh.sh <workload> <first seed> [N] [seconds]
W=$1; F=$2; N=${3:-6}; S=${4:-20}
mkdir -p chiprun_out
k=0
for set in 1 2; do for i in $(seq 1 $N); do
  k=$((k+1))
  python3 benchmark/run.py --workload $W --seed $((F+k)) --seconds $S --trace 0 2>/dev/null \
    | grep -a "^setup\|^{\|^compare" | sed "s/^/set$set run$i seed$((F+k)) /" \
    | tee -a chiprun_out/sets_fresh_$W.txt | grep -av " compare .* ok$" | cut -c1-700
done; done
k=$((k+1))
python3 benchmark/run.py --workload $W --seed $((F+k)) --seconds $S --trace 1 2>/dev/null \
  | grep -a "^setup\|^{\|^compare" | sed "s/^/trace run1 seed$((F+k)) /" \
  | tee -a chiprun_out/sets_fresh_$W.txt | grep -av " compare .* ok$" | cut -c1-2500
