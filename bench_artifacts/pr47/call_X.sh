set -x
mkdir -p chiprun_out
CELL=granite-4.0-h-micro.serve.chatrate
for cfg in "8 4" "4 4" "16 40"; do
  python3 bench_artifacts/pr47/vmem_probe.py $cfg --workload $CELL --seed 4700000119 --seconds 50 --trace 1 2>> chiprun_out/pr47_X.err | grep "^{" | sed "s|^|change_vmem_${cfg// /_} $CELL seed=4700000119 trace=1 |" | tee -a chiprun_out/pr47_X.out | cut -c1-600
done
