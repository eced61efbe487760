# PR 59: the parent commit as the driver lays it out for a new per-layer
# metric's traced runs — `git archive` of the parent under .scratch/parent
# (git-ignored) with this PR's BENCHMARK.json and benchmarks/ laid over it.
set -e
PARENT=${1:-11b84226ec316781522a3cf23cf636c99f874fd0}
rm -rf .scratch/parent && mkdir -p .scratch/parent
git archive $PARENT | tar -x -C .scratch/parent
cp BENCHMARK.json .scratch/parent/
cp -r benchmarks/. .scratch/parent/benchmarks/
rm -rf .scratch/parent/benchmarks/__pycache__ .scratch/parent/benchmarks/*/__pycache__
