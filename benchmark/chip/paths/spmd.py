"""The ``spmd`` path: ``parallel.SPMDTrainer.step``, one program a step, on
one device or over a data-parallel mesh (``traffic["mesh"]``)."""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

import common


class Path:
    def __init__(self, config, traffic, seed, devices):
        from mxnet_tpu import gluon
        from mxnet_tpu.parallel import SPMDTrainer, make_mesh
        mesh, sharding = None, None
        if traffic.get("mesh"):
            mesh = make_mesh(traffic["mesh"], devices=devices)
            sharding = NamedSharding(mesh, PartitionSpec(*mesh.axis_names[:1]))
        self.net = common.make_net(config, seed)
        # copies on the cell's devices: the trainer moves the net's own
        # arrays there too, and its step donates them
        self.initial = jax.device_put(
            common.parameters(self.net),
            NamedSharding(mesh, PartitionSpec()) if mesh else devices[0])
        self.pool = common.make_pool(config, traffic, seed, sharding,
                                     jnp.float32)
        self.trainer = SPMDTrainer(
            self.net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
            optimizer=traffic["optimizer"]["name"],
            optimizer_params=common.sgd_params(traffic),
            dtype=jnp.dtype(traffic["dtype"]))

    def dispatch(self, i):
        return self.trainer.step(*self.pool[i % len(self.pool)])

    def wait(self, loss):
        loss.block_until_ready()
        return loss

    def state(self):
        """Every parameter and optimizer state, for the placement check."""
        return common.parameters(self.net) + jax.tree_util.tree_leaves(
            self.trainer._opt_state)
