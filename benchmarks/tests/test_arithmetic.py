"""The yardstick's arithmetic against values worked out by hand."""

import os

import pytest

from benchmarks import harness
from benchmarks.models import bert, gpt

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    return harness.load_json("configs", name + ".json")


def test_percentile_is_nearest_rank():
    assert harness.percentile([4, 1, 3, 2], 50) == 2
    assert harness.percentile([4, 1, 3, 2], 100) == 4
    assert harness.percentile(list(range(1, 101)), 99) == 99
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([7], 95) == 7
    assert harness.percentile([], 95) is None
    # 20 samples: the 95th percentile is the 19th, one sample beyond it
    assert harness.percentile(list(range(20)), 95) == 18


def test_latency_is_timed_from_the_due_instant():
    # due at 1.0 and 2.0; the first was submitted late (at 1.4, say) and
    # got its token at 1.5: 500 ms, not 100.  The second failed: the
    # window's length, 30 s.
    assert harness.due_latencies_ms([1.0, 2.0], [1.5, None], 30.0) == \
        [500.0, 30000.0]


def test_token_gaps_pool_all_requests():
    got = harness.token_gaps_ms([[0.0, 0.010, 0.030], [5.0], [1.0, 1.5]])
    assert got == pytest.approx([10.0, 20.0, 500.0])


def test_seeds_beyond_32_signed_bits():
    import jax

    big = 2 ** 31 + 12345
    assert 0 <= harness.numpy_seed(big) < 2 ** 32
    assert harness.numpy_seed(big) != harness.numpy_seed(big + 1)
    a, b = harness.seed_key(big), harness.seed_key(big - 2 ** 31)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()


def test_gpt_model_flops_per_token_by_hand():
    c = config("gpt2-xl-d24")
    # per layer 12 d^2 = 12 * 1600^2 = 30,720,000; 24 layers 737,280,000;
    # head 1600 * 50257 = 80,411,200; sum 817,691,200; times 6
    assert gpt.matmul_params(c) == 817_691_200
    # attention 12 * 24 * 1024 * 1600 = 471,859,200, halved (causal)
    assert gpt.model_flops_per_token(c, 1024) == \
        6 * 817_691_200 + 235_929_600 == 5_142_076_800
    whole = config("gpt2-xl")
    assert gpt.matmul_params(whole) == 48 * 30_720_000 + 80_411_200


def test_bert_model_flops_per_token_by_hand():
    c = config("bert-large")
    # per layer 12 * 1024^2 = 12,582,912; 24 layers 301,989,888; MLM
    # transform 1,048,576; decoder 1024 * 30522 = 31,254,528; pooler and
    # NSP (1,048,576 + 2048) once per 128 positions = 8208
    per_token = 301_989_888 + 1_048_576 + 31_254_528
    assert bert.matmul_params(c, 128) == per_token + 8208
    # attention 12 * 24 * 128 * 1024 = 37,748,736, not halved
    assert bert.model_flops_per_token(c, 128) == \
        6 * (per_token + 8208) + 37_748_736 == 2_043_555_936


def test_flash_attention_cost_by_hand():
    c = config("gpt2-xl-d24")
    flops, nbytes = gpt.flash_attention_cost(c, 4, 1024)
    # one matmul over the causal half: 2 * (4*25) * 1024^2 * 64 / 2
    mm = 2 * 100 * 1024 * 1024 * 64 // 2
    assert mm == 6_710_886_400
    assert flops == 24 * 7 * mm
    tensor, lse = 100 * 1024 * 64 * 2, 100 * 1024 * 4
    assert nbytes == 24 * (12 * tensor + 2 * lse)


def test_every_seed_gets_the_same_schedule_and_its_own_tokens():
    from benchmarks.traffic import poisson_lengths as pl

    mix = harness.load_json("traffic", "chat.json")
    c = config("gpt2-xl")
    a = pl.timeline(mix, seed=1, seconds=30, config=c, family=gpt)
    b = pl.timeline(mix, seed=2 ** 31 + 9, seconds=30, config=c, family=gpt)
    assert len(a) == len(b) == round(mix["rate_rps"] * 30)
    schedule = lambda tl: [(t, len(p), n) for t, p, n in tl]
    assert schedule(a) == schedule(b)                  # the same work ...
    assert [p for _, p, _ in a] != [p for _, p, _ in b]  # ... other tokens
    assert a == pl.timeline(mix, seed=1, seconds=30, config=c, family=gpt)
    assert all(t < 30 for t, _, _ in a) and a[-1][0] > 28
    assert [t for t, _, _ in a] == sorted(t for t, _, _ in a)
    lo, hi = mix["prompt_tokens"]
    assert all(lo <= len(p) <= hi and len(p) + n <= mix["max_total_tokens"]
               and max(p) < c["vocab_size"] for _, p, n in a)
    # another deal is one more mix file with another shape_seed
    dealt = pl.timeline(dict(mix, shape_seed=3), seed=1, seconds=30,
                        config=c, family=gpt)
    assert len(dealt) == len(a) and schedule(dealt) != schedule(a)


def test_the_observer_stamps_tokens_by_its_own_clock():
    """What the program writes into a request's stamps is not read."""
    import time
    import types

    from benchmarks.runners.serve import TokenObserver

    req = types.SimpleNamespace(out=[], done=False, token_times=["never"])
    obs = TokenObserver(tick=0.001)
    obs.start()
    t0 = time.perf_counter()
    obs.watch(req)
    for n in (1, 2):          # one token, then two at once
        time.sleep(0.05)
        req.out.extend([7] * n)
    time.sleep(0.02)
    req.done = True
    obs.stop()
    first, second, third = obs.times[0]
    assert 0.05 <= first - t0 < 0.07 and second == third
    # a stamp is late by up to a tick and its look, so the second may follow
    # the first by that much under the 50 ms the tokens lay apart
    assert 0.05 - 2 * obs.tick <= second - first < 0.07
    assert obs.finished[0] >= third and obs.late_max < 0.05


def test_the_stall_watch_says_where_the_main_thread_stood():
    import time

    watch = harness.StallWatch(tick=0.01, after=0.1)
    with watch:
        watch.beat()
        a = time.perf_counter()
        time.sleep(0.3)       # the stall
        b = time.perf_counter()
        watch.beat()
    said = watch.between(a, b)
    assert 20 <= said["watch_ticks"] <= 31 and said["process_cpu_s"] < 0.2
    assert said["watch_late_ms_max"] < 50
    (stack,) = said["main_thread_at"]   # one stack for the one stall
    assert "test_the_stall_watch_says_where_the_main_thread_stood" in stack[-1]
    assert watch.between(b, b + 1)["main_thread_at"] == []


def test_the_update_is_held_against_the_reference_s_own_step():
    """A chain of two one-weight blocks, loss = w_head * w2 * w1 * x:
    gradients by hand, and systems that made the reference's step, none,
    the opposite one, and one twice as long in the first block only."""
    import jax.numpy as jnp

    from benchmarks.reference import adam_step

    lr = 0.1
    rest = {"x": jnp.float32(1.0), "h": jnp.float32(2.0)}
    blocks = [{"w": jnp.float32(3.0)}, {"w": jnp.float32(-0.5)}]
    stages = (rest, blocks, lambda r: r["x"], lambda x, p: x * p["w"],
              lambda r, x: r["h"] * x)
    # every gradient is far above eps, so each weight moves by lr against
    # its gradient's sign: x -3, h -1.5, w1 -1, w2 +6
    after = ({"x": 1.0 + lr, "h": 2.0 + lr}, [{"w": 3.0 + lr},
                                              {"w": -0.5 - lr}])

    def system(scale_rest, scale_1, scale_2):
        f = lambda a, p, s: jnp.float32(p + s * (a - p))
        return ({k: f(after[0][k], rest[k], scale_rest) for k in rest},
                [{"w": f(after[1][0]["w"], 3.0, scale_1)},
                 {"w": f(after[1][1]["w"], -0.5, scale_2)}])

    def against(*scales):
        before, _, update = adam_step.losses_around_first_step(
            stages, lr=lr, system_after=system(*scales))
        assert before == pytest.approx(-3.0)
        return update

    assert against(1, 1, 1) == pytest.approx(
        {"size": 1.0, "down_gradient": 1.0, "down_gradient_least": 1.0})
    assert against(0, 0, 0) == pytest.approx(
        {"size": 0.0, "down_gradient": 0.0, "down_gradient_least": 0.0},
        abs=1e-6)
    assert against(-1, -1, -1)["down_gradient"] == pytest.approx(-1.0)
    # |g| = 3 + 1.5 (rest), 1 (block 1), 6 (block 2)
    assert against(1, 2, 1) == pytest.approx(
        {"size": 5 / 4, "down_gradient": 12.5 / 11.5,
         "down_gradient_least": 1.0})
    assert against(1, 1, -1)["down_gradient_least"] == pytest.approx(-1.0)
    assert adam_step.losses_around_first_step(stages, lr=lr)[2] is None
