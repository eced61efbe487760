# PR 44, second session, chip call A: the cell as it is handed in (rate 0.80,
# runner serve_agree: logit_margin 1.0 and top1_agreement_floor 0.97) on six
# fresh seeds and one traced run, then the controls through the same check.
set -x
mkdir -p chiprun_out
W=command-a-plus-d4.serve.mixedlen
for s in 2147001001 2146002003 2145003007 2144004011 2143005013 2142006017; do
  python3 benchmarks/run.py --workload $W --seed $s --seconds 50 --trace 0 2>> chiprun_out/s2A.err | tee -a chiprun_out/s2A.out | grep -v "^\[20" | cut -c1-1300
done
python3 benchmarks/run.py --workload $W --seed 2141007019 --seconds 50 --trace 1 2>> chiprun_out/s2A.err | tee -a chiprun_out/s2A.out | grep -v "^\[20" | cut -c1-5000
python3 bench_artifacts/pr44/sabotage.py --seconds 25 2>> chiprun_out/s2A_sab.err | tee chiprun_out/s2A_sab.out | grep -v "^\[20\|^#" | cut -c1-700
tail -c 1200 chiprun_out/s2A.err
