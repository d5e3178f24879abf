"""Fused conv-epilogue layers: BatchNorm(+residual add)+ReLU as one block.

The ResNet bottleneck hot path writes the conv output to HBM and then
reads it back for BatchNorm statistics, again for the normalize, and the
normalized copy again for the ReLU/add — the traffic docs/perf.md's
roofline names as the training-step ceiling. These layers route the
whole epilogue through one op (_contrib_fused_bn_relu /
_contrib_fused_bn_add_relu), which lowers to the composed XLA chain by
default and to the fused Pallas kernels (ops/pallas_kernels.py
fused_bn_act) where MXTPU_FUSED_EPILOGUE=1 at trace time.

Both subclass BatchNorm so they hold the standard gamma/beta/running_*
parameters and so graph passes that match
``isinstance(block, BatchNorm)`` — notably the int8 BN-folding pass
(contrib/quantization.py fold_batchnorm) — keep working; the
``_epilogue`` attribute tells such passes which tail (relu / add+relu)
must survive the fold.

Checkpoint note: each fused block's OWN parameter set is exactly a
BatchNorm's, but adopting them in the V1 ResNet Sequential bodies
removes the separate Activation children, so the index-based child
paths of ``save_parameters`` checkpoints shift (e.g. old
``body.3.weight`` -> ``body.2.weight``). Channel-last V1 checkpoints
saved before the adoption need a one-time key remap to load.
"""
from __future__ import annotations

from .basic_layers import BatchNorm

__all__ = ["FusedBatchNormReLU", "FusedBatchNormAddReLU"]


class FusedBatchNormReLU(BatchNorm):
    """``relu(BatchNorm(x))`` in one fused op (conv -> BN -> ReLU
    epilogue). Same parameters/semantics as ``BatchNorm`` + ``Activation
    ('relu')``; channel-last input is required for the Pallas path (the
    op falls back to the composed lowering otherwise)."""

    _epilogue = "relu"

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        out, mean, var = F.contrib.fused_bn_relu(
            x, gamma, beta, running_mean, running_var,
            eps=self._epsilon, momentum=self._momentum,
            fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats, axis=self._axis)
        self._update_running_stats(running_mean, running_var, mean, var)
        return out

    def __repr__(self):
        return f"{type(self).__name__}(axis={self._axis})"


class FusedBatchNormAddReLU(BatchNorm):
    """``relu(BatchNorm(x) + residual)`` in one fused op — the ResNet
    block tail. Called with two inputs: ``block(x, residual)``."""

    _epilogue = "add_relu"

    def infer_shape_from_inputs(self, x, residual=None):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean,
                  self.running_var):
            p.shape_hint((c,))

    def hybrid_forward(self, F, x, residual, gamma, beta, running_mean,
                       running_var):
        out, mean, var = F.contrib.fused_bn_add_relu(
            x, residual, gamma, beta, running_mean, running_var,
            eps=self._epsilon, momentum=self._momentum,
            fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats, axis=self._axis)
        self._update_running_stats(running_mean, running_var, mean, var)
        return out

    def __repr__(self):
        return f"{type(self).__name__}(axis={self._axis})"
