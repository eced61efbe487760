"""Records the small device traces the trace-reduction tests read.

    chiprun -- python benchmarks/tests/data/record_trace.py

Two steps of a 2-layer GPT (d_model 256, 4 heads of 64, seq 256, micro
batch 2: the smallest shape that still takes the flash kernel) through
`deepspeed_tpu.initialize`, and a few steps of `ServeEngine` on the same
widths, each under `jax.profiler` with the benchmark's annotations.  The
`.xplane.pb` files land in `chiprun_out/trace_small/`; copy the train one
to `benchmarks/tests/data/train_small.xplane.pb`.  A summary of planes,
lines and event names goes to stdout, so the reduction can be written
against what the chip really emits.
"""

import collections
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "trace_small")


def summarize(path, top=12):
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names = collections.Counter()
            dur = collections.Counter()
            first = None
            n = 0
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if first is None:
                    first = {"name": ev.name, "start_ns": ev.start_ns,
                             "duration_ns": ev.duration_ns,
                             "stats": {k: str(v)[:80] for k, v in ev.stats}}
            lines.append({"line": line.name, "events": n, "first": first,
                          "top": [[k, names[k], dur[k]]
                                  for k, _ in dur.most_common(top)]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def trace_to(name, fn):
    import jax

    d = os.path.join(OUT, name)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    flat = os.path.join(OUT, name + ".xplane.pb")
    os.replace(path, flat)
    return flat


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm import make_mesh
    from deepspeed_tpu.models import GPT, gpt2_config
    from deepspeed_tpu.serving import ServeConfig, ServeEngine

    os.makedirs(OUT, exist_ok=True)
    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "kind": jax.devices()[0].device_kind}))
    cfg = gpt2_config("nano", num_layers=2, num_heads=4, d_model=256,
                      vocab_size=1024, max_seq_len=256,
                      shard_activations=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), mpu=make_mesh(devices=jax.devices()[:1]),
        config_params={
            "train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "bf16": {"enabled": True},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2}, "mesh": {"data": 1},
            "steps_per_print": 0})
    rs = np.random.RandomState(0)

    def batch():
        t = rs.randint(0, 1024, (2, 257)).astype(np.int32)
        return t[:, :-1], t[:, 1:]

    def step(b):
        loss = engine.forward(b)
        engine.backward()
        engine.step()
        return loss

    for _ in range(3):
        float(step(batch()))

    def train_two():
        prev = None
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.feed"):
                b = batch()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                loss = step(b)
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.read_loss"):
                    float(prev)
            prev = loss
        with jax.profiler.TraceAnnotation("bench.read_loss"):
            float(prev)
        time.sleep(0.002)

    p_train = trace_to("train_small", train_two)
    print(json.dumps({"trace": p_train, "bytes": os.path.getsize(p_train)}))
    print(json.dumps(summarize(p_train)))

    del engine
    model = GPT(gpt2_config("nano", num_layers=2, num_heads=4, d_model=256,
                            vocab_size=1024, max_seq_len=256,
                            shard_activations=False,
                            param_dtype=jnp.bfloat16))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    serve = ServeEngine(model, params, ServeConfig(
        block_size=16, num_blocks=65, max_batch=4, prefill_chunk=64,
        max_seq_len=256))
    prompts = [rs.randint(0, 1024, (n,)).tolist() for n in (20, 70, 130)]
    serve.generate(prompts, 4)

    def serve_some():
        with jax.profiler.TraceAnnotation("bench.submit"):
            for p in prompts:
                serve.submit(p, 6)
        serve.run()

    p_serve = trace_to("serve_small", serve_some)
    print(json.dumps({"trace": p_serve, "bytes": os.path.getsize(p_serve)}))
    print(json.dumps(summarize(p_serve)))
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump({"train": summarize(p_train, 40),
                   "serve": summarize(p_serve, 40)}, f)


if __name__ == "__main__":
    main()
