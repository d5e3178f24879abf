"""The ``score_causal_lm`` path: ``score_lm`` for a zoo language model with
one output head and no multi-token-prediction module, with ``score_lm``'s
dispatch, wait and state over a net, a pool and a loss of its own. A step is
``LMLoss()(net(tokens), labels)`` outside ``autograd.record()``: the
hybridized net's inference program, then the hybridized loss's, both in the
traffic's ``dtype``; nothing recomputed, no trainer, nothing updated. The
batches are ``spmd_causal_lm``'s: tokens (batch, T), labels the stream
shifted by one.

The weights are the benchmark's: one jitted call makes them on the device
from the seed, every matrix normal with the configuration's ``init_std``
and every other parameter by the initialiser the model declares for it
(``DRAWS``: a recurrent layer's decays and step and its convolutions take
the model's own draws, norms 1), in the type they are served in; the net
takes them as it takes a checkpoint's (``Parameter.set_data``). The float32
values they were rounded from do not stay on the chip: ``produced`` makes
them again from the same key for the reference, once the window has closed
and the net is let go, and hands the kept logits back on the host so that
the reference's float32 weights and logits fit beside them.
``traffic["weights_mantissa_bits"]`` (a control's traffic file only) rounds
every matrix to that many mantissa bits before the cast; the reference
still gets the unrounded values.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np

import score_lm
import spmd_causal_lm


def _uniform(key, shape, init):
    return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) \
        * init._kwargs["scale"]


def _log_uniform(key, shape, init):
    low, high = init._kwargs["low"], init._kwargs["high"]
    return jnp.log(low + (high - low) * jax.random.uniform(key, shape))


def _inverse_softplus_step(key, shape, init):
    low, high, floor = (init._kwargs[k] for k in ("low", "high", "floor"))
    dt = jnp.exp(jnp.log(low) + jax.random.uniform(key, shape)
                 * (jnp.log(high) - jnp.log(low)))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))


# the model's initialisers, by class, as draws of jax.random on the device:
# the same distributions as the host-side ``Initializer``s, not their values
DRAWS = {"Uniform": _uniform, "LogUniform": _log_uniform,
         "InverseSoftplusStep": _inverse_softplus_step}


class Path(score_lm.Path):
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
        # what ``make`` keeps: shapes and initialisers, not the parameters,
        # whose data it would hold on the chip after the net is let go
        specs = [(p.shape, p.init) for p in params]
        std, served = config["init_std"], jnp.dtype(traffic["dtype"])
        bits = traffic.get("weights_mantissa_bits")

        def make(key, serve):
            out = []
            for (shape, init), k in zip(specs, jax.random.split(key,
                                                                len(specs))):
                if init is None:                           # a matrix
                    a = std * jax.random.normal(k, shape, jnp.float32)
                    if serve and bits is not None:
                        a = jax.lax.reduce_precision(
                            a, exponent_bits=8, mantissa_bits=bits)
                elif isinstance(init, str):
                    a = jnp.full(shape, {"ones": 1.0, "zeros": 0.0}[init],
                                 jnp.float32)
                else:
                    a = DRAWS[type(init).__name__](k, shape, init)
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
        self.loss = LMLoss()
        self.loss.hybridize()
        self.pool = spmd_causal_lm.make_pool(config, traffic, seed)
        self._batches = [(from_jax(t, ctx), from_jax(l, ctx))
                         for t, l in self.pool]
        self._kept = None

    def produced(self):
        """(float32 weights, per-sequence losses (B,), (logits,)) of the last
        step the loop completed on ``pool[0]``, the logits on the host; lets
        the net go."""
        loss, logits = self._kept
        out = np.asarray(loss._data), (np.asarray(logits._data),)
        del self.net, self.loss, self._kept, self._batches, loss, logits
        gc.collect()
        return (self._make(self._key, False),) + out
