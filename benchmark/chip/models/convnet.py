"""Plain float32 building blocks shared by the convolutional references.

No framework code: ``jax.numpy`` and ``lax`` only. A ``Plain`` walks an
architecture once. Given the zoo net's initial parameters in the order the
architecture declares them (convolution weight; BatchNorm gamma, beta,
running mean, running variance; dense weight, bias) it computes the
training-mode forward pass, and it counts the multiply-adds of every
convolution and dense layer from the shapes as it goes. Given no parameters
(under ``jax.eval_shape``) it makes zeros of the declared shapes, which is
how ``flops_per_sample`` counts without a net.

``fault`` builds a deliberately wrong model, for the tests that show the
comparison can fail: ``"running_stats"`` normalises with the stored running
statistics instead of the batch's, ``("drop_branch", k)`` leaves out the
k-th residual add or concatenated branch it meets, counted from 0.
"""
import jax.numpy as jnp
from jax import lax


class Plain:
    def __init__(self, params, layout, eps, fault=None):
        self.params = None if params is None else iter(params)
        self.layout, self.eps, self.fault = layout, eps, fault
        self.caxis = layout.index("C")
        self.macs = 0  # multiply-adds of the whole batch, forward
        self.joins = 0  # residual adds and concatenations met so far

    def take(self, shape):
        if self.params is None:
            return jnp.zeros(shape, jnp.float32)
        w = next(self.params)
        if tuple(w.shape) != tuple(shape):
            raise ValueError(f"the net's next parameter is {w.shape}, the "
                             f"reference expects {shape}")
        return w.astype(jnp.float32)

    def channels(self, x):
        return x.shape[self.caxis]

    def conv(self, x, cout, kernel, stride=1, pad=(0, 0)):
        kh, kw = kernel
        cin = self.channels(x)
        chan_last = self.caxis == 3
        w = self.take((cout, kh, kw, cin) if chan_last
                      else (cout, cin, kh, kw))
        y = lax.conv_general_dilated(
            x, w, (stride, stride), [(pad[0], pad[0]), (pad[1], pad[1])],
            dimension_numbers=(self.layout, "OHWI" if chan_last else "OIHW",
                               self.layout))
        self.macs += y.size * kh * kw * cin
        return y

    def bn(self, x):
        """Training mode: the batch's own mean and biased variance."""
        c = self.channels(x)
        gamma, beta, run_mean, run_var = (self.take((c,)) for _ in range(4))
        axes = tuple(a for a in range(4) if a != self.caxis)
        shape = [1] * 4
        shape[self.caxis] = c
        if self.fault == "running_stats":
            mean, var = run_mean, run_var
        else:
            mean = jnp.mean(x, axes)
            var = jnp.mean(jnp.square(x - mean.reshape(shape)), axes)
        inv = gamma * lax.rsqrt(var + self.eps)
        return (x - mean.reshape(shape)) * inv.reshape(shape) \
            + beta.reshape(shape)

    def conv_bn_relu(self, x, cout, kernel, stride=1, pad=(0, 0)):
        return jnp.maximum(self.bn(self.conv(x, cout, kernel, stride, pad)), 0)

    def pool(self, x, kind, size, stride, pad=0):
        """Max pooling pads with -inf; average pooling counts the padding
        (MXNet's default, count_include_pad=True)."""
        dims, strides, pads = [1] * 4, [1] * 4, [(0, 0)] * 4
        for a in range(4):
            if a not in (0, self.caxis):
                dims[a], strides[a], pads[a] = size, stride, (pad, pad)
        if kind == "max":
            return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)
        return lax.reduce_window(x, 0.0, lax.add, dims, strides, pads) \
            / (size * size)

    def drop(self):
        """True at the join where the fault asks for a branch to be left
        out."""
        self.joins += 1
        return self.fault == ("drop_branch", self.joins - 1)

    def dense(self, x, units):
        x = x.reshape(x.shape[0], -1)
        w, b = self.take((units, x.shape[1])), self.take((units,))
        self.macs += x.shape[0] * units * x.shape[1]
        return x @ w.T + b


def softmax_cross_entropy(logits, label):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = logits - jnp.max(logits, -1, keepdims=True)
    logp = logp - jnp.log(jnp.sum(jnp.exp(logp), -1, keepdims=True))
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.mean(picked)


def count_flops(forward, config):
    """FLOPs of one training step per sample: 2 for each multiply-add of
    every convolution and dense layer, forward, times 3 (the backward pass
    computes a gradient for the input and one for the weights of each). The
    usual model-FLOPs convention: BatchNorm, activations, pooling, the loss
    and the optimizer are not counted, nor is anything recomputed."""
    import jax
    shape = [1, config["image"], config["image"], config["image"]]
    shape[config["layout"].index("C")] = 3
    net = Plain(None, config["layout"], config["bn_eps"])
    jax.eval_shape(lambda x: forward(net, x, config),
                   jax.ShapeDtypeStruct(tuple(shape), jnp.float32))
    return 2 * 3 * net.macs
