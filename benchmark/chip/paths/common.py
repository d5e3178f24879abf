"""What the training paths share: the zoo net, the pool of batches, SGD.

Everything is made from ``seed``: the weights through ``mx.random.seed``
(Xavier, as a zoo user gets them), the batches by one jitted ``jax.random``
program on the device.
"""
import jax
import jax.numpy as jnp


def make_net(config, seed, ctx=None):
    """The model-zoo net with every deferred shape resolved by one batch-1
    forward, as a Gluon user does before handing it to a trainer."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(seed)
    net = vision.get_model(config["zoo"]["model"], **config["zoo"]["kwargs"])
    if "dropout" in config:  # see the configuration's "assumed"
        net.apply(lambda b: isinstance(b, nn.Dropout)
                  and setattr(b, "_rate", config["dropout"]))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net(mx.nd.zeros((1,) + sample_shape(config), ctx=ctx))
    return net


def sample_shape(config):
    shape = [config["image"]] * 3
    shape[config["layout"].index("C") - 1] = 3
    return tuple(shape)


def parameters(net):
    """The net's parameter arrays in the order the architecture declares
    them (the names carry process-wide counters, the order does not)."""
    return [p.data()._data for p in net.collect_params().values()]


def make_pool(config, traffic, seed, sharding, dtype):
    """``traffic["pool"]`` distinct (data, label) batches on the device:
    uniform [0, 1) images in ``dtype`` and uniform float32 labels, placed by
    ``sharding`` (None: the default device)."""
    n, batch = traffic["pool"], traffic["batch"]
    shape = (batch,) + sample_shape(config)

    def make(key):
        keys = jax.random.split(key, 2 * n)
        return [(jax.random.uniform(keys[2 * i], shape, jnp.float32)
                 .astype(dtype),
                 jax.random.randint(keys[2 * i + 1], (batch,), 0,
                                    config["classes"]).astype(jnp.float32))
                for i in range(n)]

    # seeds run a little past 2**31: fold the high bits in, do not truncate
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    return jax.jit(make, out_shardings=sharding)(key)


def sgd_params(traffic):
    opt = traffic["optimizer"]
    return {"learning_rate": opt["lr_at_256"] * traffic["batch"] / 256,
            "momentum": opt["momentum"]}
