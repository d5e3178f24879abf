"""What the algorithm needs in a step of the window kernel the
``mimo_v2_5`` configuration runs (``ops/pallas_kernels.py:
blocked_attention`` under a window, ``mx_attention_window_fwd``):
``kernel_costs``, beside the reference and not in it, which keeps to the
forward pass's mathematics, as ``models/olmo_hybrid_7b_kernels.py`` has it.
``metrics/kernel_roofline.py`` reads it."""
from mimo_v2_5 import keys_a_query, kind_sizes


def kernel_costs(config, batch):
    """{kernel: (FLOPs, bytes)} a step over the window layers held: the
    band's products alone (each query against the keys of its window, q.k
    and p.v), 2 FLOPs a multiply-add; q and o at the query heads' count,
    k and v at the model's own KV heads (the program repeats them to the
    queries' count on the way in: that is its cost, not the algorithm's),
    each read or written once in bf16, the log-sum-exp written in float32."""
    c, t = config, config["tokens_per_sample"]
    layers = list(c["hybrid_layer_pattern"]).count(1)
    heads = c["swa_num_attention_heads"]
    kv, qk, dv, _, _, _ = kind_sizes(c, True)
    tokens = batch * t * layers
    return {"mx_attention_window_fwd": (
        tokens * 2 * heads * keys_a_query(c, True, t) * (qk + dv),
        tokens * (2 * (heads * (qk + dv) + kv * (qk + dv)) + 4 * heads))}
