#!/bin/bash
# Several runs of one cell, one after another (one process holds the chip):
#   bash benchmark/tools/runs.sh <tag> <workload> <seconds> <trace> "<extra args>" <seed>...
# Result lines go to chiprun_out/<tag>.jsonl, each run's stderr to
# chiprun_out/<tag>.<seed>.err; a one-line digest of every run is printed.
tag=$1; wl=$2; secs=$3; tr=$4; extra=$5; shift 5
mkdir -p chiprun_out
for seed in "$@"; do
  t0=$(date +%s)
  python3 benchmark/run.py --workload "$wl" --seed "$seed" --seconds "$secs" --trace "$tr" $extra \
    > chiprun_out/$tag.$seed.out 2> chiprun_out/$tag.$seed.err
  rc=$?
  t1=$(date +%s)
  tail -n 1 chiprun_out/$tag.$seed.out >> chiprun_out/$tag.jsonl
  echo "run $tag seed=$seed rc=$rc wall=$((t1-t0))s $(tail -n 1 chiprun_out/$tag.$seed.out | python3 benchmark/tools/digest.py)"
  if [ $rc -ne 0 ]; then grep -v "Warn\|warn" chiprun_out/$tag.$seed.err | tail -n 15; fi
  rm -f chiprun_out/$tag.$seed.out
done
