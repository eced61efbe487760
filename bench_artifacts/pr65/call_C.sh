# PR 65, call C: the tree git would commit (.scratch/f65) against the parent
# (.scratch/p65): `chatrate` again, two more pairs (call A's second pair
# held one 1.8 s stall of the engine alone on the change's side), then one
# pair each of the delta-rule state (`longchat`), the bare layers
# (`reasoning`) and the convolution's kept rows (`assist`).
CHANGE=$PWD/.scratch/f65 TAG=C CELLS="granite-4.0-h-micro.serve.chatrate qwen3-next-80b-a3b-d12.serve.longchat nemotron-3-nano-30b-a3b-e16.serve.reasoning lfm2-24b-a2b-e8.serve.assist" SINGLE="qwen3-next-80b-a3b-d12.serve.longchat nemotron-3-nano-30b-a3b-e16.serve.reasoning lfm2-24b-a2b-e8.serve.assist" SEEDS="3065700163 865700167 1265800179 2165900211 1165900223" sh bench_artifacts/pr65/call_pairs.sh
