# usage: _set.sh <tag> <workload> [<workload> ...]  -- six runs of each, one per seed
tag=$1; shift
mkdir -p chiprun_out
for w in "$@"; do
  for seed in 1001 1002 2147484651 1004 1005 3000000006; do
    python3 benchmark/run.py --workload $w --seed $seed --seconds 30 --trace 0 > chiprun_out/set_${tag}_${w}_${seed}.out 2> chiprun_out/set_${tag}_${w}_${seed}.err
    echo "rc=$? $w $seed $(tail -n 1 chiprun_out/set_${tag}_${w}_${seed}.out | cut -c1-260)"
  done
done
