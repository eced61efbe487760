# PR 47, chip call B: the micro-benchmark once more, as committed (the
# kernel as it is kept); then the step at low and at high load — the change
# through `benchmarks/sweep.py` at 3 and 7 requests/s (PR 46's sweep read
# the parent: itl_ms_median 38.2-38.4 at every rate, PERF.md §6), and the
# parent at 3 beside it; then two more pairs of the claimed cell
# (call_A.sh, no traced pair) and one pair of `gpt2-xl.serve.chat`,
# whose programs are the parent's bytes.
set -x
mkdir -p chiprun_out
ROOT=$PWD; P=$ROOT/.scratch/pr47_parent
CELL=granite-4.0-h-micro.serve.chatrate
python3 bench_artifacts/pr47/kernel_probe.py 2>> chiprun_out/pr47_B.err | cut -c1-250
python3 benchmarks/sweep.py --workload $CELL --rates 3,7 --seconds 50 2>> chiprun_out/pr47_B.err | grep "^{" | sed "s|^|change |" | tee -a chiprun_out/pr47_B_sweep.out | cut -c1-900
(cd $P && python3 benchmarks/sweep.py --workload $CELL --rates 3 --seconds 50 2>> $ROOT/chiprun_out/pr47_B.err | grep "^{" | sed "s|^|parent |" | tee -a $ROOT/chiprun_out/pr47_B_sweep.out | cut -c1-900)
TAG=B TRACE_SEED= SEEDS="4705000653 4706000761" bash bench_artifacts/pr47/call_A.sh
TAG=B_chat CELL=gpt2-xl.serve.chat TRACE_SEED= SEEDS="4707000877" bash bench_artifacts/pr47/call_A.sh
