set -x
mkdir -p chiprun_out
W=command-a-plus-d4.serve.mixedlen
for s in 2147483777 2147480123 2099999989 1987654321 2147311111 2133333337; do
  python3 benchmarks/run.py --workload $W --seed $s --seconds 50 --trace 0 2>> chiprun_out/C.err | tee -a chiprun_out/C.out | grep -v "^\[20" | cut -c1-900
done
python3 benchmarks/run.py --workload $W --seed 2122222229 --seconds 50 --trace 1 2>> chiprun_out/C.err | tee -a chiprun_out/C.out | grep -v "^\[20" | cut -c1-3000
python3 .scratch/sabotage.py --seconds 25 --only h_system_weights_rounded_to_fp8_e4m3 2>> chiprun_out/sabC.err | tee chiprun_out/sabC.out | grep -v "^\[20\|^#" | cut -c1-700
python3 benchmarks/sweep.py --workload $W --rates 1.1,1.05 --seconds 50 2>> chiprun_out/sweepC.err | tee chiprun_out/sweepC.out | cut -c1-1500
tail -c 800 chiprun_out/C.err
