"""The ``spmd_causal_lm`` path: a zoo language model with one output head
and no multi-token-prediction module, trained by
``parallel.SPMDTrainer.step(tokens, labels)``, one program a step, Adam.
``spmd_lm`` is the path of a model whose MTP module reads one token ahead.

The net comes from the zoo by the configuration's own keys
(``model_zoo.text.config_keys``) and is initialised on the host as a Gluon
user's is; the trainer moves it to the chip and allocates Adam's means and
variances at the first ``dispatch``, after the harness has run the reference
on ``initial`` and let it go.
"""
import jax
import jax.numpy as jnp

import common
import spmd_lm


def make_pool(config, traffic, seed):
    """``traffic["pool"]`` (tokens, labels) batches on the device: tokens
    (batch, T) int32 and labels (batch, T) float32, one stream of T + 1
    uniform ids from the vocabulary slice and the same shifted by one."""
    n, batch = traffic["pool"], traffic["batch"]
    t, vocab = config["tokens_per_sample"], config["vocab_size"]

    def make(key):
        out = []
        for k in jax.random.split(key, n):
            s = jax.random.randint(k, (batch, t + 1), 0, vocab, jnp.int32)
            out.append((s[:, :t], s[:, 1:].astype(jnp.float32)))
        return out

    # seeds run a little past 2**31: fold the high bits in, do not truncate
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    return jax.jit(make)(key)


class Path(spmd_lm.Path):
    """``spmd_lm``'s dispatch, wait and state over a net, a pool and a loss
    of this path's own."""

    def __init__(self, config, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.model_zoo import get_model
        from mxnet_tpu.gluon.model_zoo.text import LMLoss, config_keys
        from mxnet_tpu.parallel import SPMDTrainer
        mx.random.seed(seed)
        model = config["zoo"]["model"]
        self.net = get_model(model, remat=traffic["remat"],
                             **{k: config[k] for k in config_keys(model)})
        self.net.initialize(mx.init.Normal(config["init_std"]))
        self.initial = jax.device_put(common.parameters(self.net), devices[0])
        self.pool = make_pool(config, traffic, seed)
        opt = dict(traffic["optimizer"])
        self.trainer = SPMDTrainer(
            self.net, LMLoss(), optimizer=opt.pop("name"),
            optimizer_params=opt, dtype=jnp.dtype(traffic["dtype"]))
