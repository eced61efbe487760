"""StableHLO hashes of the toy serving programs of tests/test_program_scopes.py
for the routed families, as lowered (and with the lines sorted, which tells a
re-ordering of independent operations from a changed operation)."""
import hashlib, sys, os, json
root = sys.argv[1]
sys.path.insert(0, root); sys.path.insert(0, os.path.join(root, "tests"))
os.chdir(root)
import jax
from test_program_scopes import FAMILIES
from deepspeed_tpu.serving import ServeConfig, ServeEngine
import re
out = {}
for family in sys.argv[2:]:
    model, serve = FAMILIES[family]()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    calls = engine._program_calls()
    for name in ("prefill", "decode"):
        program, args = calls[name]
        text = program.lower(*args).as_text()
        lines = text.splitlines()
        # an operation's text without the value names it defines and reads
        bare = sorted(re.sub(r"%[\w#:]+", "%", ln) for ln in lines)
        out[f"{family}.{name}"] = {
            "as_lowered": hashlib.sha256(text.encode()).hexdigest()[:16],
            "operations_sorted": hashlib.sha256("\n".join(bare).encode()).hexdigest()[:16],
            "lines": len(lines)}
    engine.close()
print(json.dumps(out, indent=1))
