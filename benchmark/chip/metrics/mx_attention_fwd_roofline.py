"""Roofline share of the blocked attention's ``mx_attention_fwd`` kernel
(``ops/pallas_kernels.py``): see ``kernel_roofline.py``. From the device
trace."""
import kernel_roofline


def read(run):
    return kernel_roofline.read(run, "mx_attention_fwd")
