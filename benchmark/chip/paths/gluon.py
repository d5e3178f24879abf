"""The ``gluon`` path, the loop MXNet users write: a hybridized net on
``mx.tpu()``, ``autograd.record`` / ``backward`` and ``gluon.Trainer.step``.

Mixed precision is MXNet's idiom: ``net.cast(dtype)``, batches in that dtype,
the net's output cast to float32 for the loss, and a ``multi_precision``
optimizer that keeps float32 master weights. The same arithmetic as the
``spmd`` path's ``dtype=``.
"""
import jax
import jax.numpy as jnp

import common


class Path:
    def __init__(self, config, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, gluon
        self.autograd, self.batch = autograd, traffic["batch"]
        ctx = mx.tpu()
        self.net = common.make_net(config, seed, ctx=ctx)
        self.initial = [a.copy() for a in common.parameters(self.net)]
        dtype = traffic["dtype"]
        if dtype != "float32":
            self.net.cast(dtype)
        self.net.hybridize()
        self.trainer = gluon.Trainer(
            self.net.collect_params(), traffic["optimizer"]["name"],
            dict(common.sgd_params(traffic),
                 multi_precision=dtype != "float32"))
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.pool = common.make_pool(config, traffic, seed, None,
                                     jnp.dtype(dtype))
        self.batches = [(mx.nd.from_jax(d, ctx=ctx), mx.nd.from_jax(l, ctx=ctx))
                        for d, l in self.pool]

    def dispatch(self, i):
        data, label = self.batches[i % len(self.batches)]
        with self.autograd.record():
            loss = self.loss_fn(self.net(data).astype("float32"), label)
        loss.backward()
        self.trainer.step(self.batch)
        return loss.mean()._data

    def wait(self, loss):
        loss.block_until_ready()
        return loss

    def state(self):
        params = list(self.net.collect_params().values())
        states = jax.tree_util.tree_leaves(
            [u.states for u in self.trainer._updaters])
        return [p.data()._data for p in params] \
            + [p.grad()._data for p in params if p.grad_req != "null"] \
            + [s._data for s in states]
