"""Roofline share of the blocked attention's window kernel
``mx_attention_window_fwd`` (``ops/pallas_kernels.py``): see
``kernel_roofline.py``. From the device trace."""
import kernel_roofline


def read(run):
    return kernel_roofline.read(run, "mx_attention_window_fwd")
