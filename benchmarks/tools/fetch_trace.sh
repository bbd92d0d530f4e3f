#!/bin/bash
# A short traced run of a cell, with its .xplane.pb brought back under
# chiprun_out/ to be looked at by hand:
#   bash benchmarks/tools/fetch_trace.sh <workload> <seed> <seconds>
mkdir -p chiprun_out
python3 benchmarks/run.py --workload "$1" --seed "$2" --seconds "$3" --trace 1 \
  > chiprun_out/fetch_$1.out 2> chiprun_out/fetch_$1.err
echo "rc=$?"; tail -n 1 chiprun_out/fetch_$1.out | cut -c1-1500
f=$(ls .bench_out/$1/trace/plugins/profile/*/*.xplane.pb | tail -n 1)
ls -l "$f"; gzip -c "$f" > chiprun_out/fetch_$1.xplane.pb.gz; ls -l chiprun_out/fetch_$1.xplane.pb.gz
