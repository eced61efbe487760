"""Records the small serving trace on which the scope reader is checked.

    chiprun -- python benchmarks/tests/data/record_scoped_trace.py

`record_trace.py`'s serving stretch over a 4-layer GPT of d_model 1024, 8
heads of 128, vocabulary 8192, bf16 — large enough that the microsecond
between two operations is under 2 % of a program's run (at d_model 256 a
decode step is 30 us and idles 3 % of it between its operations), small
enough that the capture stays ~2 MB; its decode step takes the paged
kernel — with a `TraceRecorder` attached before the capture, so that the
engine's programs say which instruction lies under which scope
(`program_scopes`, PR 59), and with the capture inside a `bench.window`
annotation as a traced benchmark run has it.  Two files land in
`chiprun_out/trace_scoped/`: `serve_scoped.xplane.pb` (gzip it into
`benchmarks/tests/data/`) and `serve_scoped.scopes.json`, the recorder's
`program_scopes` events (copy it beside).  Stdout says, for each
program, how many of the trace's `XLA Ops` events inside its runs carry
an instruction name the compiled text has, and how many of those the map
holds (kept in `serve_scoped.names.json` too): the names the trace prints
must be the names `as_text()` prints.
"""

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "trace_scoped")


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import GPT, gpt2_config
    from deepspeed_tpu.monitor import tracing
    from deepspeed_tpu.serving import ServeConfig, ServeEngine
    from deepspeed_tpu.serving.programs import STAGES

    os.makedirs(OUT, exist_ok=True)
    print(json.dumps({"devices": [str(d) for d in jax.devices()],
                      "kind": jax.devices()[0].device_kind}))
    model = GPT(gpt2_config("nano", num_layers=4, num_heads=8, d_model=1024,
                            vocab_size=8192, max_seq_len=256,
                            shard_activations=False,
                            param_dtype=jnp.bfloat16))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    serve = ServeEngine(model, params, ServeConfig(
        block_size=16, num_blocks=65, max_batch=4, prefill_chunk=64,
        max_seq_len=256))
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 8192, (n,)).tolist() for n in (20, 70, 130)]
    serve.generate(prompts, 4)
    recorder = tracing.TraceRecorder(os.path.join(OUT, "spans"))
    serve.attach_tracing(tracer=recorder)

    d = os.path.join(OUT, "serve_scoped")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.submit"):
                for p in prompts:
                    serve.submit(p, 6)
            serve.run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    flat = os.path.join(OUT, "serve_scoped.xplane.pb")
    os.replace(path, flat)
    events = [e for e in recorder.last_events()
              if e["name"] == "program_scopes"]
    recorder.close()
    with open(os.path.join(OUT, "serve_scoped.scopes.json"), "w") as f:
        json.dump(events, f)
    print(json.dumps({"trace": flat, "bytes": os.path.getsize(flat),
                      "scopes": [[e["args"]["program"], e["args"]["seconds"],
                                  len(e["args"]["instructions"]),
                                  len(json.dumps(e))] for e in events]}))

    # the names the trace prints against the names the compiled text has
    texts = {}
    for name, (program, args) in serve._program_calls().items():
        text = program.lower(*args).compile().as_text()
        texts[tracing.program_name(text)] = set(re.findall(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", text, re.M))
    maps = tracing.scope_maps(events)
    profile = jax.profiler.ProfileData.from_file(flat)
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        runs = [(e.start_ns, e.start_ns + e.duration_ns,
                 e.name.split("(", 1)[0])
                for e in lines.get("XLA Modules", ())]
        seen = {}
        for e in lines.get("XLA Ops", ()):
            prog = next((p for a, b, p in runs if a <= e.start_ns < b), None)
            if prog not in texts:
                continue
            m = re.match(r"^%?([\w.\-]+) = ", e.name)
            got = seen.setdefault(prog, {"events": 0, "in_text": 0,
                                         "in_map": 0, "missing": []})
            got["events"] += 1
            got["in_text"] += bool(m and m.group(1) in texts[prog])
            got["in_map"] += bool(m and m.group(1) in maps[prog])
            if not (m and m.group(1) in texts[prog]) and \
                    len(got["missing"]) < 5:
                got["missing"].append(e.name[:120])
        print(json.dumps({"plane": plane.name, "names": seen}))
        with open(os.path.join(OUT, "serve_scoped.names.json"), "w") as f:
            json.dump({"plane": plane.name, "names": seen}, f)
    print("\n".join(tracing.scope_table(
        tracing.device_scope_times(profile, events), STAGES)))


if __name__ == "__main__":
    main()
