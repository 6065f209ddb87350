"""Plain reference of ResNet9 (cifar10-fast topology, no batch norm) and its
cross-entropy loss: jax.numpy and lax only, float32, nothing of the program.

prep conv -> layer1 conv+pool -> residual(2 convs) -> layer2 conv+pool ->
layer3 conv+pool -> residual(2 convs) -> maxpool 4 -> bias-free linear ->
x output_scale. Every conv is 3x3, stride 1, same padding, no bias, ReLU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import lowp


class Model:
    def __init__(self, config: dict):
        ch = config["channels"]
        cin = config["image_channels"]
        self.scale = float(config["output_scale"])
        self.num_classes = int(config["num_classes"])
        self.hw = int(config["image_size"])
        self.ch = ch
        self.cin = cin
        # the tree the program's flat vector ravels (sorted keys)
        self.shapes = {
            "prep": {"Conv_0": {"kernel": (3, 3, cin, ch["prep"])}},
            "layer1": {"Conv_0": {"kernel": (3, 3, ch["prep"], ch["layer1"])}},
            "res1": {
                "res1": {"Conv_0": {"kernel": (3, 3, ch["layer1"], ch["layer1"])}},
                "res2": {"Conv_0": {"kernel": (3, 3, ch["layer1"], ch["layer1"])}},
            },
            "layer2": {"Conv_0": {"kernel": (3, 3, ch["layer1"], ch["layer2"])}},
            "layer3": {"Conv_0": {"kernel": (3, 3, ch["layer2"], ch["layer3"])}},
            "res3": {
                "res1": {"Conv_0": {"kernel": (3, 3, ch["layer3"], ch["layer3"])}},
                "res2": {"Conv_0": {"kernel": (3, 3, ch["layer3"], ch["layer3"])}},
            },
            "linear": {"kernel": (ch["layer3"], self.num_classes)},
        }

    # -- weights from the seed, one jitted call ---------------------------

    def make(self, key):
        """The weights of a key: uniform(+-1/sqrt(fan_in)) kernels."""
        leaves, treedef = jax.tree_util.tree_flatten(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, shape in enumerate(leaves):
            fan_in = 1
            for n in shape[:-1]:
                fan_in *= n
            lim = fan_in ** -0.5
            out.append(jax.random.uniform(
                jax.random.fold_in(key, i), shape, jnp.float32, -lim, lim))
        return jax.tree_util.tree_unflatten(treedef, out)

    def init(self, seed: int):
        return jax.jit(self.make)(jax.random.key(seed))

    # -- forward and loss ---------------------------------------------------

    @staticmethod
    def _conv(x, p, pool=0, cast=None):
        k = p["Conv_0"]["kernel"]
        y = jax.lax.conv_general_dilated(
            lowp(x, cast), lowp(k, cast), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = jnp.maximum(y, 0)
        if pool:
            y = jax.lax.reduce_window(
                y, -jnp.inf, jax.lax.max, (1, pool, pool, 1),
                (1, pool, pool, 1), "VALID")
        return y

    def logits(self, params, x, cast=None):
        def c(x, p, pool=0):
            return self._conv(x, p, pool, cast)

        out = c(x, params["prep"])
        out = c(out, params["layer1"], 2)
        out = out + c(c(out, params["res1"]["res1"]), params["res1"]["res2"])
        out = c(out, params["layer2"], 2)
        out = c(out, params["layer3"], 2)
        out = out + c(c(out, params["res3"]["res1"]), params["res3"]["res2"])
        w = min(4, out.shape[1])
        out = jax.lax.reduce_window(out, -jnp.inf, jax.lax.max,
                                    (1, w, w, 1), (1, w, w, 1), "VALID")
        out = out.reshape(out.shape[0], -1)
        return (lowp(out, cast)
                @ lowp(params["linear"]["kernel"], cast)) * self.scale

    def loss_sum(self, params, batch, cast=None):
        """One client's summed cross-entropy over its valid images, and
        their count."""
        logits = self.logits(params, batch["inputs"], cast)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, batch["targets"].astype(jnp.int32)[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * batch["mask"]), jnp.sum(batch["mask"])

    # -- work of one round (for round_mfu) ----------------------------------

    def train_flops(self, batch_shapes: dict) -> float:
        """Forward + backward model FLOPs of one round: 2 x MACs forward,
        backward twice the forward (copy of bench.resnet9_train_flops_per_
        image, times the round's image slots)."""
        ch, h = self.ch, self.hw
        macs = self.cin * ch["prep"] * 9 * h * h
        macs += ch["prep"] * ch["layer1"] * 9 * h * h
        h //= 2
        macs += 2 * ch["layer1"] ** 2 * 9 * h * h
        macs += ch["layer1"] * ch["layer2"] * 9 * h * h
        h //= 2
        macs += ch["layer2"] * ch["layer3"] * 9 * h * h
        h //= 2
        macs += 2 * ch["layer3"] ** 2 * 9 * h * h
        macs += ch["layer3"] * self.num_classes
        images = batch_shapes["inputs"][0] * batch_shapes["inputs"][1]
        return 3.0 * 2.0 * macs * images
