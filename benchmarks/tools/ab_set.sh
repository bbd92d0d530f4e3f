#!/bin/bash
# Parent against change in one call, on the same chip:
#   bash benchmarks/tools/ab_set.sh <parent_dir> <workload> <tag> <trace> <seed> [<seed> ...]
# <parent_dir> holds the parent commit (`git archive`) with this tree's
# BENCHMARK.json and benchmarks/ laid over it, as the driver lays them. Both
# sides run every seed, in the order parent, change, change, parent, ...
# Output: chiprun_out/<tag>_{p,c}_<seed>.out and .err; spread.py reads them.
parent=$1; w=$2; tag=$3; trace=$4; shift 4
mkdir -p chiprun_out
out=$PWD/chiprun_out
one() {  # side dir seed
  ( cd "$2" && python3 benchmarks/run.py --workload "$w" --seed "$3" --seconds 51 \
      --trace "$trace" > "$out/${tag}_$1_$3.out" 2> "$out/${tag}_$1_$3.err" )
  echo "rc=$? side=$1 seed=$3"; tail -n 1 "$out/${tag}_$1_$3.out" | cut -c1-300
}
flip=0
for s in "$@"; do
  if [ $flip -eq 0 ]; then one p "$parent" "$s"; one c . "$s"
  else one c . "$s"; one p "$parent" "$s"; fi
  flip=$((1 - flip))
done
