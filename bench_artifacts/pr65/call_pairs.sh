# PR 65, a chip call of pairs (PR 64's script, several cells a call): for
# each cell of CELLS, parent, change, change, parent with the profiler off
# — a pair shares its seed.  The parent is the parent commit (4a8262e)
# unpacked under .scratch/p65 (this PR adds no benchmark file to lay over
# it); the change is the working tree (CHANGE: another directory, the final
# tree's).  CELLS, TAG and SEEDS (two a cell, in order) from the
# environment (SINGLE: cells that get one pair, parent then change, and
# one seed).  `.out`: the result lines; `.check`: each run's `# {...}`
# line of the comparison that decides `correct`.
set -x
mkdir -p chiprun_out
ROOT=$PWD; C=${CHANGE:-$ROOT}; P=$ROOT/.scratch/p65; TAG=${TAG:-A}
run() {
  (cd $1 && python3 benchmarks/run.py --workload $3 --seed $4 --seconds 50 --trace 0 2>> $ROOT/chiprun_out/pr65_$TAG.err | tee -a $ROOT/chiprun_out/pr65_$TAG.full | grep "^{" | sed "s|^|$2 $3 seed=$4 |" | tee -a $ROOT/chiprun_out/pr65_$TAG.out | cut -c1-700)
  grep "^# {" $ROOT/chiprun_out/pr65_$TAG.full | tail -n 1 | sed "s|^|$2 $3 seed=$4 |" | tee -a $ROOT/chiprun_out/pr65_$TAG.check | cut -c1-400
}
set -- $SEEDS
for cell in $CELLS; do
  run $P parent $cell $1; run $C change $cell $1
  case " ${SINGLE-} " in
    *" $cell "*) shift 1 ;;                 # one pair for a cell of SINGLE
    *) run $C change $cell $2; run $P parent $cell $2; shift 2 ;;
  esac
  date
done
tail -c 600 chiprun_out/pr65_$TAG.err
