"""Model FLOP/s utilisation: samples per second of the traced run's untraced
part, times the FLOPs a step needs per sample (``flops``), over chips times
the published bf16 peak of the device kind (peaks.json). A kind that is not
in the table has no MFU."""
import samples_per_s


def flops(run):
    """models/<config>.py's ``flops_per_sample``: 2 per multiply-add,
    forward x 3, recomputed operations not counted. A path that does not
    train (its traffic file says ``"trains": false``) makes the forward
    pass alone: a third of it."""
    per_sample = run["reference"].flops_per_sample(run["config"])
    return per_sample if run["traffic"].get("trains", True) else per_sample / 3


def read(run):
    rate = samples_per_s.read(run)
    if run["peaks"] and rate:
        return 100.0 * rate * flops(run) \
            / (run["cell"]["chips"] * run["peaks"]["bf16_flops_per_s"])
