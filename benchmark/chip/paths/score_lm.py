"""The ``score_lm`` path: a zoo language model scored, not trained. A step is
``LMLoss(net(tokens), labels)`` outside ``autograd.record()``: the hybridized
net's inference program, then the hybridized loss's, both in the traffic's
``dtype``; no recomputation, no trainer, no optimizer, nothing updated. It is
``Module.score`` or a Gluon validation loop on a checkpoint: per-sequence
losses of held-out data.

The weights are the benchmark's, not the program's: one jitted call makes
them on the device from the seed (every matrix normal with the
configuration's ``init_std``, norms 1, the router's bias and counters 0, as
the configuration's ``assumed`` states) in the type they are served in, and
the net takes them as it takes a checkpoint's (``Parameter.set_data``). The
float32 values they were rounded from do not stay on the chip: ``produced``
makes them again from the same key for the reference, once the window has
closed and the net is let go. ``traffic["weights_mantissa_bits"]`` (the
control's traffic file only) rounds every matrix to that many mantissa bits
before the cast; the reference still gets the unrounded values.
"""
import gc

import jax
import jax.numpy as jnp

import common
import spmd_lm


class Path:
    def __init__(self, config, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.model_zoo import get_model
        from mxnet_tpu.gluon.model_zoo.text import LMLoss, config_keys
        from mxnet_tpu.ndarray.ndarray import from_jax
        model = config["zoo"]["model"]
        self.net = get_model(model, **{k: config[k]
                                       for k in config_keys(model)})
        self.net.collect_params().setattr("grad_req", "null")
        params = list(self.net.collect_params().values())
        # a parameter that declares no initializer is a matrix
        assert all((p.init is None) == (len(p.shape) > 1) for p in params)
        std, served = config["init_std"], jnp.dtype(traffic["dtype"])
        bits = traffic.get("weights_mantissa_bits")

        def make(key, serve):
            out = []
            for p, k in zip(params, jax.random.split(key, len(params))):
                if p.init is None:
                    a = std * jax.random.normal(k, p.shape, jnp.float32)
                    if serve and bits is not None:
                        a = jax.lax.reduce_precision(
                            a, exponent_bits=8, mantissa_bits=bits)
                else:
                    a = jnp.full(p.shape, {"ones": 1.0, "zeros": 0.0}[p.init],
                                 jnp.float32)
                out.append(a.astype(served) if serve else a)
            return out

        # seeds run a little past 2**31: fold the high bits in
        self._key = jax.random.fold_in(
            jax.random.PRNGKey(seed % 2**31), seed // 2**31)
        self._make = jax.jit(make, static_argnums=1, out_shardings=
                             jax.sharding.SingleDeviceSharding(devices[0]))
        ctx = mx.tpu()
        for p, a in zip(params, self._make(self._key, True)):
            p.set_data(from_jax(a, ctx))
        self.net.cast(traffic["dtype"])  # the parameters' declared type too
        self.net.hybridize()
        self.loss = LMLoss(config["mtp_loss_weight"])
        self.loss.hybridize()
        self.pool = spmd_lm.make_pool(config, traffic, seed)
        self._batches = [(from_jax(t, ctx), from_jax(l, ctx))
                         for t, l in self.pool]
        self._kept = None

    def dispatch(self, i):
        tokens, labels = self._batches[i % len(self._batches)]
        heads = self.net(tokens)
        loss = self.loss(heads, labels)
        if i % len(self._batches) == 0:
            self._kept = (loss, heads)
        return loss

    def wait(self, loss):
        """The batch's loss: the mean of its sequences'."""
        return loss.asnumpy().mean()

    def state(self):
        return common.parameters(self.net)

    def produced(self):
        """(float32 weights, per-sequence losses (B,), the heads' logits) of
        the last step the loop completed on ``pool[0]``; lets the net go."""
        loss, heads = self._kept
        heads = heads if isinstance(heads, (list, tuple)) else (heads,)
        out = loss._data, tuple(h._data for h in heads)
        del self.net, self.loss, self._kept, self._batches, loss, heads
        gc.collect()
        return (self._make(self._key, False),) + out
