# usage: _traces.sh <workload> ...  -- three traced runs of each
mkdir -p chiprun_out
for w in "$@"; do
  for seed in 4001 2147487650 4003; do
    python3 benchmark/run.py --workload $w --seed $seed --seconds 30 --trace 1 > chiprun_out/trace_${w}_${seed}.out 2> chiprun_out/trace_${w}_${seed}.err
    echo "rc=$? $w $seed $(tail -n 1 chiprun_out/trace_${w}_${seed}.out | cut -c1-200)"
  done
done
