# PR 65, call B: the tree git would commit (.scratch/f65) against the parent
# (.scratch/p65): 16 paged slots at the highest step rate (`chat`), the
# latent rows (`chatgen`), the learned selection (`longctx`).
CHANGE=$PWD/.scratch/f65 TAG=B CELLS="gpt2-xl.serve.chat deepseek-v2-lite-d9.serve.chatgen glm-5.2-d5.serve.longctx" SEEDS="2165400137 1165400151 3065500163 865500167 2165600137 1165600151" sh bench_artifacts/pr65/call_pairs.sh
