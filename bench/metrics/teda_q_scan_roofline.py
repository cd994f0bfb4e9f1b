"""The Q-format TEDA kernel's share of its roofline, in %: the least
time the chip could take to move the useful bytes of the work the
traced stretch retired (`bytes/teda_q_scan_kernel.py`) at the HBM peak
(`peaks.json`), over the kernel's summed device time."""


def read(ctx):
    return ctx.roofline("teda_q_scan_kernel")
