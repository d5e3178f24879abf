"""The chunked delta rule's share of its roofline: the least time the chip
could take for what the algorithm needs in a step (the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, ``kernel_costs(config, batch)
["mx_delta_rule"]`` found as ``kernel_roofline.py`` finds a kernel's) over
the device seconds a step under the ``mx.delta_rule`` scope
(``mx_delta_rule_ms.py``). XLA lowers the rule: it has no kernel of its own
whose instruction name a trace could give, so its scope stands for one.
Nothing where the trace has no op under the scope, the configuration no
such cost or the device no peak."""
import kernel_roofline
import mx_delta_rule_ms


def read(run):
    reference, peaks = run["reference"], run["peaks"]
    costs = getattr(reference, "kernel_costs", None) \
        or kernel_roofline._beside(reference)
    ms = mx_delta_rule_ms.read(run)
    if not (costs and ms and peaks):
        return None
    cost = costs(run["config"], run["traffic"]["batch"]).get("mx_delta_rule")
    if cost is None:
        return None
    flops, nbytes = cost
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
