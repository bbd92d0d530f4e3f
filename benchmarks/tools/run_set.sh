#!/bin/bash
# One set of a cell's runs in one call, each with another seed:
#   bash benchmarks/tools/run_set.sh <workload> <tag> <trace> <seed> [<seed> ...]
# Each run's output goes to chiprun_out/<tag>_<seed>.out and .err; the end of
# its result line is echoed. benchmarks/tools/spread.py reads the files.
w=$1; tag=$2; trace=$3; shift 3
mkdir -p chiprun_out
for s in "$@"; do
  python3 benchmarks/run.py --workload "$w" --seed "$s" --seconds 51 --trace "$trace" \
    > "chiprun_out/${tag}_$s.out" 2> "chiprun_out/${tag}_$s.err"
  echo "rc=$? seed=$s"; tail -n 1 "chiprun_out/${tag}_$s.out" | cut -c1-420
done
