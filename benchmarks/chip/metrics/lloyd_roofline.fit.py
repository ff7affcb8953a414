"""Share of the Lloyd kernel's roofline: the least time of the traced
fits' Lloyd steps (work.sampled_fit_lloyd, from shapes) over the device
time of the ``lloyd`` Pallas kernel in the trace."""
from work import roofline_share, sampled_fit_lloyd

# The ``lloyd`` kernel: a ``pallas_call`` (name stack) made in
# ``kernels/lloyd.py`` (source line), in the fold and in the merge alike.
SCOPE = r"pallas_call"
SOURCE = r"kernels/lloyd\.py:"


def read(ctx):
    if ctx.trace is None:
        return None
    spec = ctx.cell.config["fit"]["spec"]
    data = ctx.cell.config["data"]
    flops, nbytes = sampled_fit_lloyd(spec, data["n"], data["dim"])
    fits = ctx.trace.steps
    return roofline_share(fits * flops, fits * nbytes,
                          ctx.trace.kernel_s(SCOPE, SOURCE), ctx.device_kind)
