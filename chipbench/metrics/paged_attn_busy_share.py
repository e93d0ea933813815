"""The fused paged-attention kernel's device time over the device's busy
time, in the traced window.  Silent where no kernel event is found."""

from chipbench.metrics.paged_attn_roofline import kernel_seconds


def read(v):
    if v.trace is None or v.trace.busy_s <= 0:
        return None
    secs = kernel_seconds(v.trace) / max(v.trace.n_devices, 1)
    return 100.0 * secs / v.trace.busy_s if secs > 0 else None
