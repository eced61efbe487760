# PR 59: the files git would commit, and nothing else, under
# .scratch/final (git-ignored): run after `git add -A`.
set -e
rm -rf .scratch/final && mkdir -p .scratch/final
git archive $(git write-tree) | tar -x -C .scratch/final
