"""What the algorithm needs in a step of the Pallas kernels the
``nemotron_3_nano_30b_a3b`` configuration runs: ``kernel_costs``, as
``models/glm_4_7_flash.py`` has it for its own. It stands beside the
reference, not in it, because ``tests/test_nemotron_h.py`` holds the
reference to having none and only a PR outside the benchmark may edit that
test (PERF.md section 7); ``metrics/kernel_roofline.py`` looks here where a
reference has no ``kernel_costs``."""


def kernel_costs(config, batch):
    """{kernel: (FLOPs, bytes)} a training step over the attention layers
    held: grouped-KV causal attention at half the square, every operand read
    and every result written once, bf16 (the log-sum-exp and the row sums
    float32). The queries have ``num_attention_heads`` heads; the keys and
    values the algorithm has to read, and the gradients it has to write for
    them, have ``num_key_value_heads`` (the program repeats them to the
    queries' count on the way in: that is its cost, not the algorithm's)."""
    c, t, d = config, config["tokens_per_sample"], config["head_dim"]
    sites = c["hybrid_override_pattern"].count("*")
    q_rows = batch * c["num_attention_heads"] * t
    kv_rows = batch * c["num_key_value_heads"] * t
    half = q_rows * t / 2 * 2               # FLOPs of one (T, T/2) product
    return {
        # reads q, k, v; writes o and the log-sum-exp
        "mx_attention_fwd": (sites * half * 2 * d,
                             sites * (q_rows * (2 * 2 * d + 4)
                                      + kv_rows * 2 * 2 * d)),
        # reads q, do, k, v, the log-sum-exp and the row sums; writes dq
        "mx_attention_dq": (sites * half * 3 * d,
                            sites * (q_rows * (2 * 3 * d + 8)
                                     + kv_rows * 2 * 2 * d)),
        # reads q, do, k, v and the two row vectors; writes dk and dv
        "mx_attention_dkv": (sites * half * 4 * d,
                             sites * (q_rows * (2 * 2 * d + 8)
                                      + kv_rows * 2 * 4 * d)),
    }
