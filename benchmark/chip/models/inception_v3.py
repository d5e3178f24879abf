"""Plain reference of Inception-v3 as the model zoo builds it.

Szegedy et al., arXiv:1512.00567, in the form of MXNet's
``gluon/model_zoo/vision/inception.py``: every convolution is followed by
BatchNorm (eps 1e-3) and ReLU; stride-1 convolutions are SAME-padded unless
marked valid, stride-2 ones are valid; 3x A (35x35), B, 4x C (17x17), D,
2x E (8x8), an 8x8 average pooling and one dense layer. Channel-first, the
zoo's only layout for it. The zoo's Dropout(0.5) before the dense layer is
run at rate 0 (configs/inception_v3.json says why).

Tolerance: as models/resnet50_v1.py.
"""
import jax
import jax.numpy as jnp

from convnet import Plain, count_flops, softmax_cross_entropy

TOLERANCE = 5e-3

# (channels, (kh, kw), stride, valid) chains; "avg"/"max" are the pools
A = lambda p: [[(64, 1)], [(48, 1), (64, 5)], [(64, 1), (96, 3), (96, 3)],
               ["avg", (p, 1)]]
B = [[(384, 3, 2)], [(64, 1), (96, 3), (96, 3, 2)], ["max"]]
C = lambda c: [[(192, 1)], [(c, 1), (c, (1, 7)), (192, (7, 1))],
               [(c, 1), (c, (7, 1)), (c, (1, 7)), (c, (7, 1)), (192, (1, 7))],
               ["avg", (192, 1)]]
D = [[(192, 1), (320, 3, 2)],
     [(192, 1), (192, (1, 7)), (192, (7, 1)), (192, 3, 2)], ["max"]]
FAN = [[(384, (1, 3))], [(384, (3, 1))]]
E = [[(320, 1)], [(384, 1), FAN], [(448, 1), (384, 3), FAN],
     ["avg", (192, 1)]]
MIXED = [A(32), A(64), A(64), B, C(128), C(160), C(160), C(192), D, E, E]


def unit(net, x, spec):
    if spec == "avg":
        return net.pool(x, "avg", 3, 1, 1)
    if spec == "max":
        return net.pool(x, "max", 3, 2)
    if isinstance(spec, list):
        return split(net, x, spec)
    cout, k, stride, valid = (tuple(spec) + (1, False))[:4]
    k = (k, k) if isinstance(k, int) else k
    pad = (0, 0) if valid or stride == 2 else (k[0] // 2, k[1] // 2)
    return net.conv_bn_relu(x, cout, k, stride, pad)


def split(net, x, branches):
    outs = []
    for chain in branches:
        y = x
        for spec in chain:
            y = unit(net, y, spec)
        outs.append(y)
    if net.drop():  # the widest branch
        i = max(range(len(outs)), key=lambda j: net.channels(outs[j]))
        outs[i] = jnp.zeros_like(outs[i])
    return jnp.concatenate(outs, net.caxis)


def forward(net, x, config):
    for spec in [(32, 3, 2), (32, 3, 1, True), (64, 3), "max", (80, 1),
                 (192, 3, 1, True), "max"]:
        x = unit(net, x, spec)
    for branches in MIXED:
        x = split(net, x, branches)
    x = net.pool(x, "avg", 8, 8)
    return net.dense(x, config["classes"])


def loss(params, data, label, config, fault=None):
    """Training-mode forward pass and softmax cross-entropy, float32."""
    with jax.default_matmul_precision("highest"):
        net = Plain(params, config["layout"], config["bn_eps"], fault)
        logits = forward(net, data.astype(jnp.float32), config)
        return softmax_cross_entropy(logits, label)


def flops_per_sample(config):
    return count_flops(forward, config)
