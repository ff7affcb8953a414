"""Share of the ADC scan kernel's roofline: the least time of the window's
scans (work.adc_scan over the real candidates of the lists the traced
queries probed; padded slots are waste, not work) over the device time of
the ``scan`` Pallas kernel in the trace."""
from work import adc_scan, roofline_share

# The ``scan`` kernel: a ``pallas_call`` (name stack) made in
# ``kernels/scan.py`` (source line).
SCOPE = r"pallas_call"
SOURCE = r"kernels/scan\.py:"


def read(ctx):
    t, c = ctx.trace, ctx.layer
    if t is None or not c.get("candidates"):
        return None
    flops, nbytes = adc_scan(c["queries"] * c["nprobe"], c["candidates"],
                             c["m"], c["codes"])
    return roofline_share(flops, nbytes, t.kernel_s(SCOPE, SOURCE),
                          ctx.device_kind)
