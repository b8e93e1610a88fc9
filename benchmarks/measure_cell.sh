#!/bin/sh
# All chip runs of one cell in ONE call, each a process of its own, every line
# written to chiprun_out/ as it comes (a call whose tail is cut loses nothing).
#
#   chiprun [--chips 4] --timeout 3000 -- sh benchmarks/measure_cell.sh <cell> <seconds> <runs per set> [sets] [traced runs]
#
# Order: one cold run (compiles; fills benchmarks/.jax_cache), then <sets> sets
# of <runs per set> runs with the same seeds in each set, then the traced runs.
cell=$1; seconds=$2; per_set=$3; sets=${4:-2}; traced=${5:-1}
out=chiprun_out/$cell; mkdir -p "$out"
run() {  # run <tag> <seed> <trace>
    echo "== $1 seed $2 trace $3 $(date +%T)"
    python3 benchmarks/run.py --workload "$cell" --seed "$2" --seconds "$seconds" --trace "$3" \
        2>"$out/$1.err" | tee "$out/$1.jsonl" | tail -n 1
    echo "   exit $? $(date +%T)"
}
run cold 2147483659 0
s=1; while [ "$s" -le "$sets" ]; do
    i=1; while [ "$i" -le "$per_set" ]; do
        run "set$s.run$i" $((3000000000 + i * 7919)) 0
        i=$((i + 1)); done
    s=$((s + 1)); done
i=1; while [ "$i" -le "$traced" ]; do
    run "traced$i" $((3100000000 + i * 104729)) 1
    i=$((i + 1)); done
