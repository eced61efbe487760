"""CIFAR-10-shaped ResNet, ZeRO-0, fp32, single process — mirrors
DeepSpeedExamples/cifar (BASELINE.json config 1): the simplest
deepspeed_tpu.initialize loop, non-transformer model, no sharding.

    python examples/cifar_resnet.py [--steps 30]
"""

from __future__ import annotations

import argparse

from common import print_curve  # noqa: E402

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.runtime.module import TrainModule


def conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ResNetSmall(TrainModule):
    """3-stage residual CNN (CIFAR scale)."""

    def __init__(self, width=16, num_classes=10):
        self.width = width
        self.num_classes = num_classes

    def init(self, rng):
        w = self.width
        ks = jax.random.split(rng, 8)
        he = lambda k, s: jax.random.normal(k, s) * np.sqrt(
            2.0 / (s[0] * s[1] * s[2]))
        return {
            "stem": he(ks[0], (3, 3, 3, w)),
            "blocks": [
                {"c1": he(ks[1 + 2 * i], (3, 3, w, w)),
                 "c2": he(ks[2 + 2 * i], (3, 3, w, w))}
                for i in range(3)],
            "head": jax.random.normal(ks[7],
                                      (w, self.num_classes)) * 0.01,
        }

    def apply(self, params, x, rng=None, train=False):
        h = jax.nn.relu(conv(x, params["stem"]))
        for bp in params["blocks"]:
            r = jax.nn.relu(conv(h, bp["c1"]))
            h = jax.nn.relu(h + conv(r, bp["c2"]))
        h = jnp.mean(h, axis=(1, 2))  # global average pool
        return h @ params["head"]

    def loss(self, params, batch, rng=None, train=True):
        x, y = batch
        logits = self.apply(params, x, rng=rng, train=train)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=ResNetSmall(),
        config_params={
            "train_batch_size": args.batch,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10,
        })

    rng = np.random.RandomState(0)
    # synthetic CIFAR: class = dominant color channel (learnable)
    losses = []
    for _ in range(args.steps):
        y = rng.randint(0, 3, args.batch)
        x = rng.rand(args.batch, 32, 32, 3).astype(np.float32) * 0.2
        x[np.arange(args.batch), :, :, y] += 0.8
        loss = engine.forward((x, y.astype(np.int32)))
        engine.backward()
        engine.step()
        losses.append(float(loss))
    print_curve("cifar_resnet zero0 fp32", losses)
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
