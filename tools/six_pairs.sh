#!/bin/bash
# The driver's protocol for a claimed gain, run by the builder first (PR 30):
# N seeds, parent and change turn about (parent, change / change, parent),
# untraced, each run through tools/step_intervals.py so that its step-interval
# record is kept; then one traced pair.  One call on the chip, ~6 min a pair.
#
#   git add -A; rm -rf bench_checkout/parent bench_checkout/change
#   mkdir -p bench_checkout/parent bench_checkout/change
#   git archive <parent commit> | tar -x -C bench_checkout/parent
#   git archive $(git write-tree) | tar -x -C bench_checkout/change
#   cp BENCHMARK.json bench_checkout/parent/; cp -r benchmark/. bench_checkout/parent/benchmark/
#   chiprun --timeout 3550 -- bash tools/six_pairs.sh [first seed] [pairs] [workload]
#
# Every result, `setup` and `intervals` line lands in chiprun_out/pairs.txt
# behind "<side> seed<n> trace<t> "; every interval in
# chiprun_out/intervals_<side>_<seed>_t<t>.json; stderr in err_<...>.txt; and
# from a side that stamps host events (PR 39) a `host_events` line and every
# event the ring held in events_<side>_<seed>_t<t>.json.
# Hand in only if every run of the change is within 0.2 % of the change's
# median and every pair is won (.claude/skills/verify/SKILL.md, PR 30).
S0=${1:-3000000101}; N=${2:-6}; W=${3:-resnet50_topk_lw_staged}
ROOT=$(cd "$(dirname "$0")/.." && pwd); OUT=$ROOT/chiprun_out; mkdir -p "$OUT"; T0=$SECONDS
run() { # side seed trace
  if [ $((SECONDS - T0)) -gt 3250 ]; then echo "skipped $1 $2 trace$3: out of time" | tee -a "$OUT/pairs.txt"; return; fi
  (cd "$ROOT/bench_checkout/$1" && python3 "$ROOT/tools/step_intervals.py" --workload "$W" --seed "$2" --seconds 20 --trace "$3" \
      --intervals_out "$OUT/intervals_$1_$2_t$3.json" --events_out "$OUT/events_$1_$2_t$3.json" 2>"$OUT/err_$1_$2_t$3.txt" \
    | grep -a "^{\|^setup\|^intervals\|^host_events\|FAIL" | sed "s/^/$1 seed$2 trace$3 /" | tee -a "$OUT/pairs.txt" | cut -c1-1500)
}
for i in $(seq 0 $((N - 1))); do
  s=$((S0 + i))
  if [ $((i % 2)) -eq 0 ]; then run parent $s 0; run change $s 0; else run change $s 0; run parent $s 0; fi
done
run parent $((S0 + 50)) 1
run change $((S0 + 50)) 1
echo "elapsed $((SECONDS - T0)) s"
