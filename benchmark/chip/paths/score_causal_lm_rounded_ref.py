"""The ``score_causal_lm_rounded_ref`` path: ``score_causal_lm``'s net,
batches, weights and step; only what the reference is given differs. Where
the float32 weights would not fit beside the reference's float32 logits
(6.86 GB of bf16 weights are 13.7 GB in float32), ``produced`` hands it the
weights made again from the seed and rounded to the traffic's ``dtype``:
the values the net served, but for a control's mantissa bits
(``traffic["weights_mantissa_bits"]``), which are never applied to them, so
a control still reads against the unrounded model. The reference computes
in float32 at ``highest`` on those values: the logits' gap then measures
the precision of the compute, not the rounding of the weights.
"""
import jax
import jax.numpy as jnp

import score_causal_lm


class Path(score_causal_lm.Path):
    def __init__(self, config, traffic, seed, devices):
        super().__init__(config, traffic, seed, devices)
        specs = [(p.shape, p.init)
                 for p in self.net.collect_params().values()]
        std, served = config["init_std"], jnp.dtype(traffic["dtype"])

        def make(key):
            """``score_causal_lm``'s weights, served, without a control's
            rounding: the same key for each array, the same draws."""
            out = []
            for (shape, init), k in zip(specs, jax.random.split(key,
                                                                len(specs))):
                if init is None:                           # a matrix
                    a = std * jax.random.normal(k, shape, jnp.float32)
                elif isinstance(init, str):
                    a = jnp.full(shape, {"ones": 1.0, "zeros": 0.0}[init],
                                 jnp.float32)
                else:
                    a = score_causal_lm.DRAWS[type(init).__name__](k, shape,
                                                                   init)
                out.append(a.astype(served))
            return out

        self._served = jax.jit(make, out_shardings=
                               jax.sharding.SingleDeviceSharding(devices[0]))

    def produced(self):
        """``score_causal_lm``'s, with the served weights in place of the
        float32 ones."""
        self._make = lambda key, serve: self._served(key)
        return super().produced()
