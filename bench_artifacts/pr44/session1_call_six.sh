set -x
mkdir -p chiprun_out
W=command-a-plus-d4.serve.mixedlen
for s in 2147483659 2147489999 2100000011 1999999973 2147400001 2123456789; do
  python3 benchmarks/run.py --workload $W --seed $s --seconds 50 --trace 0 2>> chiprun_out/six.err | tee -a chiprun_out/six.out | grep -v "^\[20" | cut -c1-1200
done
python3 benchmarks/run.py --workload $W --seed 2111111111 --seconds 50 --trace 1 2>> chiprun_out/six.err | tee -a chiprun_out/six.out | grep -v "^\[20" | cut -c1-6000
python3 .scratch/sabotage.py --seconds 25 2>> chiprun_out/sab.err | tee chiprun_out/sab.out | grep -v "^\[20\|^#" | cut -c1-700
tail -c 1500 chiprun_out/six.err
