#!/bin/bash
# The chip runs that prove a cell, in one process chain on the chip's machine:
#
#   bash bench/prove_cell.sh <cell> <seconds> <outdir>
#
# a first run (compiles), bench/calibrate.py on 12 seeds with the control
# and faults on 3, two sets of 6 runs on the same seeds, 3 traced runs.
# Each run's result line goes to <outdir>/summary.txt beside its wall time.
cell=$1; secs=$2; out=$3
mkdir -p "$out"
seeds="2147484001 2147484002 4294967401 4294967402 7000000001 7000000002"
one() {
  local tag=$1 seed=$2 trace=$3 t=$(date +%s)
  timeout 900 python3 bench/run.py --workload "$cell" --seed "$seed" --seconds "$secs" \
    --trace "$trace" > "$out/$tag.$seed.out" 2> "$out/$tag.$seed.err"
  echo "$cell $tag $seed rc=$? wall=$(( $(date +%s) - t )) $(tail -n 1 "$out/$tag.$seed.out")" \
    | tee -a "$out/summary.txt"
}
one first 2147483901 0
timeout 1800 python3 bench/calibrate.py --workload "$cell" \
  --seeds 11,12,13,14,15,16,2147483648,2147483649,4294967296,4294967297,6000000001,6000000002 \
  --fault-seeds 21,22,2147483671 --out "$out/calibrate.json" > "$out/calibrate.log" 2>&1
echo "$cell calibrate rc=$? $(tail -n 1 "$out/calibrate.log")" | tee -a "$out/summary.txt"
for set in A B; do for s in $seeds; do one "$set" "$s" 0; done; done
for s in 3100000001 3100000002 3100000003; do one T "$s" 1; done
