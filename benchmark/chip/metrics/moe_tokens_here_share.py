"""Share of the work even routing would give the held experts that they did
in the last step: the ``tokens_here`` counter ((token, expert) pairs whose
expert is held here, i.e. rows the grouped products computed) over
``batch x tokens x experts per token x held / router width``, mean over the
expert layers. 1 when the held experts do what even routing gives them, 0
when routing has left them. From the program's counters, read after the
window; a program without such layers has no reading."""
import statistics


def read(run):
    try:
        from mxnet_tpu.gluon.model_zoo.text.glm_moe_lite import DroplessMoE
    except ImportError:
        return None
    layers, config = list(DroplessMoE.instances), run["config"]
    if not layers:
        return None
    even = run["traffic"]["batch"] * config["tokens_per_sample"] \
        * config["num_experts_per_tok"] * len(config["experts_held"]) \
        / config["router_experts"]
    return statistics.fmean(
        float(m.tokens_here.data().asnumpy()[0]) / even for m in layers)
