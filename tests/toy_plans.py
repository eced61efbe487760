"""A cache plan for a cache built by hand: `serving/kv_cache.py`'s own
`cache_plan` over a layer spec and the few numbers it reads of a model's
config and a ServeConfig, so a test of the allocator names only what it
varies."""

import types

from deepspeed_tpu.models.layer_spec import LayerSpec
from deepspeed_tpu.serving.kv_cache import cache_plan


def toy_spec(attention="paged", **fields):
    return LayerSpec("layernorm", "learned", attention, "gelu_mlp", "tied",
                     1e-5, **fields)


def toy_plan(layers, heads, head_dim, block_size, max_seq_len, slots=1,
             prefill_chunk=8, spec=None, **fields):
    """The plan of `layers` layers of `heads` x `head_dim` rows under
    blocks of `block_size` and requests of at most `max_seq_len`
    positions; `spec` (or the `toy_spec` of `fields`) says what they
    keep."""
    cfg = types.SimpleNamespace(num_layers=layers, num_heads=heads,
                                head_dim=head_dim, max_seq_len=max_seq_len)
    c = types.SimpleNamespace(block_size=block_size, max_seq_len=max_seq_len,
                              prefill_chunk=prefill_chunk, max_batch=slots)
    return cache_plan(spec or toy_spec(**fields), cfg, c)
