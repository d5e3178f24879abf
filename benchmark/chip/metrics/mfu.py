"""Model FLOP/s utilisation: samples per second of the traced run's untraced
part, times the model's FLOPs per sample (models/<config>.py, 2 per
multiply-add, forward x 3), over chips times the published bf16 peak of the
device kind (peaks.json). A kind that is not in the table has no MFU."""
import samples_per_s


def read(run):
    rate = samples_per_s.read(run)
    if run["peaks"] and rate:
        return 100.0 * rate * run["reference"].flops_per_sample(run["config"]) \
            / (run["cell"]["chips"] * run["peaks"]["bf16_flops_per_s"])
