"""Plain reference of ResNet-50 v1 as the model zoo builds it.

He et al., arXiv:1512.03385, Table 1, in the form of MXNet's
``gluon/model_zoo/vision/resnet.py`` (``resnet_spec[50]``, version 1): a 7x7
stride-2 stem, 3x3 stride-2 max pooling, bottlenecks of (3, 4, 6, 3) blocks
at (256, 512, 1024, 2048) channels whose stride sits on the FIRST 1x1
convolution (MXNet's v1; the "v1.5" of other zoos strides the 3x3, which
costs 4.1 G multiply-adds where this one costs 3.9 G), a projection shortcut
where the shape changes, global average pooling and one dense layer.

Tolerance of the on-chip comparison (system in bf16 compute against this in
float32 at ``highest`` matmul precision, same initial weights, same batch):
``|dloss| / (|loss| + 1) <= TOLERANCE``. 5e-3 is chip_smoke.py's figure for
bf16 through 50 layers; what the chip measured is in PERF.md section 6. The
CPU test (tests/test_reference.py) shows a model with the batch statistics
or one residual add left out lying well outside it.
"""
import jax
import jax.numpy as jnp

from convnet import Plain, count_flops, softmax_cross_entropy

TOLERANCE = 5e-3


def forward(net, x, config):
    x = net.conv_bn_relu(x, 64, (7, 7), 2, (3, 3))
    x = net.pool(x, "max", 3, 2, 1)
    for blocks, channels in zip(config["layers"], config["channels"]):
        for b in range(blocks):
            stride = 2 if b == 0 and channels != config["channels"][0] else 1
            project = b == 0
            y = net.conv_bn_relu(x, channels // 4, (1, 1), stride)
            y = net.conv_bn_relu(y, channels // 4, (3, 3), 1, (1, 1))
            y = net.bn(net.conv(y, channels, (1, 1)))
            if project:
                x = net.bn(net.conv(x, channels, (1, 1), stride))
            x = jnp.maximum(y if net.drop() else x + y, 0)
    x = jnp.mean(x, tuple(a for a in range(4) if a not in (0, net.caxis)))
    return net.dense(x, config["classes"])


def loss(params, data, label, config, fault=None):
    """Training-mode forward pass and softmax cross-entropy, float32."""
    with jax.default_matmul_precision("highest"):
        net = Plain(params, config["layout"], config["bn_eps"], fault)
        logits = forward(net, data.astype(jnp.float32), config)
        return softmax_cross_entropy(logits, label)


def flops_per_sample(config):
    return count_flops(forward, config)
