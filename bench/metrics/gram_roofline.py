"""Share of the roofline reached by the fused sketch→Gram kernel, in percent.

Kernel time: the device time of the Pallas gram kernel's ops in the trace, matched
by the names today's ``kernels/*/gram.py`` pass to ``pallas_call``. Least time:
max(flops / peak FLOP/s, bytes / peak bytes/s) of the algorithm's own work for the
answers in the window (``bench/work.py``, peaks from ``bench/peaks.json``). On
several chips, the mean of the chips' shares. Nothing to read: no kernel ops.
"""
from bench import work

KERNELS = ("gaussian_gram", "sjlt_gram")


def read(ctx):
    least = work.least_seconds(*ctx.work, ctx.device_kind)
    shares = []
    for chip in ctx.summary.ops:
        t = ctx.summary.kernel_s(chip, KERNELS)
        if t > 0:
            shares.append(100.0 * least * ctx.window.count / t)
    return sum(shares) / len(shares) if shares else None
