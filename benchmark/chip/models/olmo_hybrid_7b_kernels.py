"""What the algorithm needs in a step of the chunked delta rule the
``olmo_hybrid_7b`` configuration runs (``ops/lm_ops.py:
gated_delta_rule_chunked``, XLA-lowered under the scope ``mx.delta_rule``):
``kernel_costs``, as ``models/nemotron_3_nano_30b_a3b_kernels.py`` has it
for its own, beside the reference and not in it, which keeps to the
forward pass's mathematics. ``metrics/mx_delta_rule_roofline.py`` reads it.
"""
from olmo_hybrid_7b import rule_macs_per_token


def kernel_costs(config, batch):
    """{name: (FLOPs, bytes)} a step over the linear-attention layers held:
    the chunked algorithm's multiply-adds at the program's chunk
    (``rule_macs_per_token``), 2 FLOPs each; q, k and v read once in bf16,
    the decay's log and beta once in float32, o written once in bf16."""
    c, t = config, config["tokens_per_sample"]
    layers = c["layer_types"].count("linear_attention")
    heads = c["linear_num_key_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    tokens = batch * t * layers
    return {"mx_delta_rule": (
        tokens * 2 * rule_macs_per_token(c),
        tokens * heads * (2 * (2 * dk + 2 * dv) + 2 * 4))}
