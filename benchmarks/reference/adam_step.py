"""The plain reference's first optimizer step, so that a training cell is
checked after a real update and not only at the initial weights.

Adam from zero moments with bias correction moves a parameter by
`lr * g / (|g| + eps)` (first moment g, second g*g), plus decoupled decay.
The gradient is taken by backpropagation through a family's `stages`, one
block at a time in float32: the block is recomputed from its kept input,
its parameters are moved at once, and its gradient is dropped, so the
reference never holds a second copy of the model's gradients.

Where the system's step cannot be the reference's (dropout on in the one
and off in the other), the loss after such a step is no yardstick: Adam's
first step moves every parameter by lr at once, the loss overshoots, and
how far depends on the draw.  Then the UPDATE is compared, group by group
(every block, and the rest), while the reference's gradient of that group
is at hand: how large the system's move is beside the reference's, and how
far it goes down the reference's gradient, `sum(g * move)`, as a share of
what the reference's own move goes down it."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _against(p, g, after, system_after):
    """[down the gradient by the system's move, by the reference's own,
    size of the system's move, of the reference's] for one group."""
    tm = jax.tree_util.tree_map

    def total(f, *trees):
        return sum(jnp.sum(a) for a in jax.tree_util.tree_leaves(
            tm(f, *trees)))

    f32 = lambda t: tm(lambda a: a.astype(jnp.float32), t)
    mine = tm(jnp.subtract, f32(after), f32(p))
    theirs = tm(jnp.subtract, f32(system_after), f32(p))
    return jnp.stack([total(jnp.multiply, g, theirs),
                      total(jnp.multiply, g, mine),
                      total(jnp.abs, theirs), total(jnp.abs, mine)])


def update_against(groups):
    """The `[4]` rows of `_against`, one a group -> what the runner
    gates: `size` (the system's whole move over the reference's),
    `down_gradient` (over all groups) and `down_gradient_least` (the
    group where the system's move follows the reference's gradient
    least: one block with a broken gradient shows here)."""
    rows = [[float(v) for v in row] for row in groups]
    total = [sum(r[i] for r in rows) for i in range(4)]
    return {"size": total[2] / total[3],
            "down_gradient": total[0] / total[1],
            "down_gradient_least": min(r[0] / r[1] for r in rows)}


def losses_around_first_step(stages, *, lr: float, eps: float = 1e-8,
                             weight_decay: float = 0.0, system_after=None):
    """-> (loss at the given weights, loss on the same batch after one
    Adam step on it, the updates compared or None).  `stages` is a family reference's
    `(rest, blocks, embed, block, head)`: `embed(rest) -> x`,
    `block(x, p) -> x` for p in blocks, `head(rest, x) -> loss`.
    With `system_after = (rest, blocks)`, the system's parameters after
    ITS first step on that batch, the third value is `update_against`'s
    comparison of the two updates."""
    rest, blocks, embed, block, head = stages
    rows = []

    def moved(p, g):
        return jax.tree_util.tree_map(
            lambda p, g: (p - lr * (g / (jnp.abs(g) + eps)
                                    + weight_decay * p)).astype(p.dtype),
            p, g)

    def moved_and_row(p, g, theirs):
        after = moved(p, g)
        return after, (None if theirs is None
                       else _against(p, g, after, theirs))

    @jax.jit
    def block_back(x, p, dy, theirs):
        dx, g = jax.vjp(block, x, p)[1](dy)
        return (dx, *moved_and_row(p, g, theirs))

    @jax.jit
    def rest_moved(r, g_head, dx, theirs):
        (g_embed,) = jax.vjp(embed, r)[1](dx)
        return moved_and_row(
            r, jax.tree_util.tree_map(jnp.add, g_head, g_embed), theirs)

    def forward(r, ps):
        x, inputs = embed(r), []
        for p in ps:
            inputs.append(x)
            x = block(x, p)
        return x, inputs

    x, inputs = forward(rest, blocks)
    before, (g_head, dx) = jax.jit(
        jax.value_and_grad(head, argnums=(0, 1)))(rest, x)
    after_blocks = [None] * len(blocks)
    their_rest, their_blocks = system_after or (None, [None] * len(blocks))
    for i in reversed(range(len(blocks))):
        dx, after_blocks[i], row = block_back(inputs.pop(), blocks[i], dx,
                                              their_blocks[i])
        rows.append(row)
    after_rest, row = rest_moved(rest, g_head, dx, their_rest)
    rows.append(row)
    x, _ = forward(after_rest, after_blocks)
    return (float(before), float(head(after_rest, x)),
            update_against(rows) if system_after else None)
