"""Imbalance of the routing in the last step: the largest over the mean of
the tokens routed to each of the router's experts, mean over the expert
layers. 1 is even routing. From the program's counters: each expert layer
keeps ``load`` on the device as aux state of the step; read here, after the
window. A program without such layers has no reading."""
import statistics


def read(run):
    try:
        from mxnet_tpu.gluon.model_zoo.text.glm_moe_lite import DroplessMoE
    except ImportError:
        return None
    loads = [m.load.data().asnumpy() for m in DroplessMoE.instances]
    ratios = [float(l.max() / l.mean()) for l in loads if l.sum() > 0]
    return statistics.fmean(ratios) if ratios else None
