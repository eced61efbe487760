#!/bin/bash
# PR 54 call E: (f) chat, chatgen and mixedlen, parent against change,
# alternating, 4 pairs each, every pair a seed of its own
bash bench_artifacts/pr54/pairs.sh Echat gpt2-xl.serve.chat 2254400011 2254400029 2254400047 2254400063
bash bench_artifacts/pr54/pairs.sh Echatgen deepseek-v2-lite-d9.serve.chatgen 2254500013 2254500031 2254500049 2254500067
bash bench_artifacts/pr54/pairs.sh Emixedlen command-a-plus-d4.serve.mixedlen 2254600017 2254600033 2254600051 2254600069
