"""Parameter scope shared by the plain references.

A reference forward pass is written once against a ``Scope``. With a
key the scope DRAWS every parameter it is asked for (the benchmark's
own seeded weights, in the checkpoint layout the served family loads:
``{"params": ..., "batch_stats": ...}``); with a tree it reads them.
So the layout is stated in one place, the forward pass itself, and the
weights come from the seed and from nothing the program computed.

The draws keep the signal alive through depth, which the families' own
initialisers do not (untrained heads then score every anchor within
1e-4 of one value, and a gate or an NMS order is decided by rounding):
conv and dense kernels are normal with variance ``gain / fan_in``, and
a drawing scope runs the forward pass on a seeded calibration input
and sets every batch norm's running mean and variance to what it sees
there, as training would have; scale is drawn near one and bias near
the configuration's ``weights.bn_bias``. Every normalised layer then
hands on unit-scale features, as a trained network does. The bias
matters for the output check: a random network whose batch norms
centre their activations on the kink of the nonlinearity is chaotic in
depth (a rounding error grows by about a fifth a layer, two orders of
magnitude over a detector's depth, which no trained network does), so
the draws put most pre-activations on the linear side, where an error
passes through a layer at the size it came in. The last layer of each head is drawn by the family from its
configuration's ``weights`` block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST

# variance-preserving gains: 1 / E[act(x)^2] for x ~ N(0, 1)
GAIN = {"silu": 2.8, "relu": 2.0}


class Scope:
    def __init__(self, key=None, tree=None, bn_bias: float = 0.0):
        self.bn_bias = bn_bias
        self.drawing = key is not None
        self.key = key
        self.tree = tree if tree is not None else {}
        self.flops = 0.0  # multiply-adds x 2 of every conv/dense applied
        self._n = 0

    def _next_key(self):
        self._n += 1
        return jax.random.fold_in(self.key, self._n)

    def get(self, collection: str, path: tuple, draw):
        """The array at ``tree[collection][path...]``; drawn by
        ``draw(key)`` when this scope draws."""
        node = self.tree.setdefault(collection, {}) if self.drawing else self.tree[collection]
        for name in path[:-1]:
            node = node.setdefault(name, {}) if self.drawing else node[name]
        if self.drawing:
            node[path[-1]] = draw(self._next_key())
        return node[path[-1]]

    def kernel(self, path: tuple, shape: tuple, gain: float):
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        std = (gain / fan_in) ** 0.5
        return self.get(
            "params", path + ("kernel",),
            lambda k: std * jax.random.normal(k, shape, jnp.float32),
        )

    def batch_norm(self, path: tuple, x, eps: float, live=None):
        """Inference-mode batch norm. A drawing scope sets the running
        statistics to those of ``x`` itself (over the rows ``live``
        marks, if given), as training on such inputs would have left
        them; scale and bias are drawn near the identity."""
        c = x.shape[-1]
        flat = x.reshape(-1, c)
        w = jnp.ones((flat.shape[0], 1)) if live is None else live.reshape(-1, 1).astype(jnp.float32)
        n = jnp.maximum(w.sum(), 1.0)
        mu = (flat * w).sum(0) / n
        scale = self.get("params", path + ("scale",),
                         lambda k: jax.random.uniform(k, (c,), jnp.float32, 0.8, 1.25))
        bias = self.get("params", path + ("bias",),
                        lambda k: self.bn_bias + 0.1 * jax.random.normal(k, (c,), jnp.float32))
        mean = self.get("batch_stats", path + ("mean",), lambda k: mu)
        var = self.get("batch_stats", path + ("var",),
                       lambda k: (jnp.square(flat - mu) * w).sum(0) / n)
        return (x - mean) * (scale * lax.rsqrt(var + eps)) + bias

    def conv(self, path: tuple, x, features: int, k: int, stride: int, pad: int, gain: float):
        w = self.kernel(path, (k, k, x.shape[-1], features), gain)
        y = lax.conv_general_dilated(
            x, w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        )
        self.flops += 2.0 * k * k * x.shape[-1] * features * y.shape[0] * y.shape[1] * y.shape[2]
        return y

    def head(self, path: tuple, x, logit_std, logit_mean, above=None):
        """The last layer of a head: a 1x1 conv with bias. A drawing
        scope rescales and biases it so that, over the calibration
        input, output channel ``j`` has mean ``logit_mean[j]`` and
        standard deviation ``logit_std[j]`` across positions: the
        configuration's ``weights`` block. ``above = (n, level)`` then
        shifts every channel's bias by one common amount so that ``n``
        of the calibration logits lie above ``level``: how many pass a
        gate is a tail count, which a mean and a spread do not pin
        (it varied tenfold from seed to seed), and a count does."""
        cin = x.shape[-1]
        logit_std = jnp.asarray(logit_std, jnp.float32)
        logit_mean = jnp.asarray(logit_mean, jnp.float32)
        features = logit_std.shape[0]
        if self.drawing:
            raw = jnp.einsum(
                "bhwi,io->bhwo", x,
                jax.random.normal(self._next_key(), (cin, features), jnp.float32),
                precision=HIGHEST,
            )
            flat = raw.reshape(-1, features)
            gain = logit_std / jnp.maximum(flat.std(0), 1e-6)
            bias = logit_mean - flat.mean(0) * gain
            if above is not None:
                n, level = above
                kth = jax.lax.top_k((flat * gain + bias).reshape(-1), n + 1)[0]
                bias = bias + level - 0.5 * (kth[n - 1] + kth[n])
            self._head = (gain, bias)
            self._n -= 1  # the kernel below redraws the same normal
        w = self.get(
            "params", path + ("kernel",),
            lambda k: (jax.random.normal(k, (cin, features), jnp.float32) * self._head[0])[None, None],
        )
        b = self.get("params", path + ("bias",), lambda k: self._head[1])
        self.flops += 2.0 * cin * features * x.shape[0] * x.shape[1] * x.shape[2]
        return jnp.einsum("bhwi,io->bhwo", x, w[0, 0], precision=HIGHEST) + b
