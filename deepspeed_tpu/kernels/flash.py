"""Registry adapters for dense causal flash attention (op 4).

The heavy lifting already lives in ops/transformer/flash_attention.py
(the Pallas streaming kernel) and ops/transformer/attention.py
(`xla_attention`, the fp32-softmax einsum chain that IS the correctness
oracle).  This module only reconciles the two signatures so
`dispatch("flash_attention", ...)` can run either side with identical
kwargs — parity is tolerance-bounded (different reduction order:
online-softmax tiles vs one fused softmax).

Both sides take BSHD `[batch, seq, heads, head_dim]` and return BSHD.
"""

from __future__ import annotations

from typing import Optional

from ..ops.transformer.attention import xla_attention
from ..ops.transformer.flash_attention import flash_attention


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           dropout_rate: float = 0.0, dropout_rng=None):
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           dropout_rate=dropout_rate,
                           dropout_rng=dropout_rng)


def flash_attention_reference(q, k, v, *, causal: bool = True,
                              scale: Optional[float] = None,
                              block_q: Optional[int] = None,
                              block_k: Optional[int] = None,
                              dropout_rate: float = 0.0,
                              dropout_rng=None):
    # block sizes are a kernel tuning knob with no oracle meaning —
    # accepted so both impls take the same kwargs, then ignored
    del block_q, block_k
    return xla_attention(q, k, v, causal=causal, scale=scale,
                         dropout_rate=dropout_rate,
                         dropout_rng=dropout_rng,
                         train=dropout_rate > 0.0)
