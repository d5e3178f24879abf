"""The ``spmd_lm`` path: a zoo language model trained by
``parallel.SPMDTrainer.step(tokens, labels)``, one program a step, Adam.

The net is initialised on the host as a Gluon user's is; the trainer moves
it to the chip and allocates Adam's means and variances at the first
``dispatch``, after the harness has run the reference on ``initial`` and let
it go: masters, that copy, the reference's activations and Adam's state do
not fit the chip together.
"""
import jax
import jax.numpy as jnp

import common

def make_pool(config, traffic, seed):
    """``traffic["pool"]`` (tokens, labels) batches on the device: tokens
    (batch, T + 1) int32, labels (batch, T, 2) float32 = the same stream
    shifted by one and by two."""
    n, batch = traffic["pool"], traffic["batch"]
    t, vocab = config["tokens_per_sample"], config["vocab_size"]

    def make(key):
        out = []
        for k in jax.random.split(key, n):
            s = jax.random.randint(k, (batch, t + 2), 0, vocab, jnp.int32)
            out.append((s[:, :t + 1], jnp.stack(
                [s[:, 1:t + 1], s[:, 2:t + 2]], -1).astype(jnp.float32)))
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    return jax.jit(make)(key)


class Path:
    def __init__(self, config, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.model_zoo import get_model
        from mxnet_tpu.gluon.model_zoo.text import CONFIG_KEYS, LMLoss
        from mxnet_tpu.parallel import SPMDTrainer
        mx.random.seed(seed)
        self.net = get_model(config["zoo"]["model"], remat=traffic["remat"],
                             **{k: config[k] for k in CONFIG_KEYS})
        self.net.initialize(mx.init.Normal(config["init_std"]))
        self.initial = jax.device_put(common.parameters(self.net), devices[0])
        self.pool = make_pool(config, traffic, seed)
        opt = dict(traffic["optimizer"])
        self.trainer = SPMDTrainer(
            self.net, LMLoss(config["mtp_loss_weight"]),
            optimizer=opt.pop("name"), optimizer_params=opt,
            dtype=jnp.dtype(traffic["dtype"]))

    def dispatch(self, i):
        return self.trainer.step(*self.pool[i % len(self.pool)])

    def wait(self, loss):
        loss.block_until_ready()
        return loss

    def state(self):
        """Every parameter, counter and optimizer state, for the placement
        check."""
        return common.parameters(self.net) + jax.tree_util.tree_leaves(
            self.trainer._opt_state)
