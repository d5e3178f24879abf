"""A Pallas kernel's share of its roofline: the least time the chip could
take for what the algorithm needs in a step (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, from ``kernel_costs`` of
``models/<config>.py``, or of ``models/<config>_kernels.py`` where the
reference has none, and ``peaks.json``) over the device seconds a step of the
kernel's call sites (the trace's ops whose HLO instruction carries the
kernel's name). Nothing where the trace has no such op, the configuration no
such function or the device no peak."""
import importlib
import pathlib


def read(run, kernel):
    costs = getattr(run["reference"], "kernel_costs", None) \
        or _beside(run["reference"])
    trace, peaks = run["trace"], run["peaks"]
    if not (costs and trace and peaks):
        return None
    seconds = trace["seconds_by_kind"].get(kernel, 0.0) / trace["steps"]
    if not seconds:
        return None
    flops, nbytes = costs(run["config"], run["traffic"]["batch"])[kernel]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def _beside(reference):
    """``kernel_costs`` of the module named after the reference's with
    ``_kernels`` behind it, in the reference's directory (on ``sys.path``
    since the harness loaded the reference from it)."""
    try:
        return importlib.import_module(
            pathlib.Path(reference.__file__).stem + "_kernels").kernel_costs
    except ImportError:
        return None
