"""device_idle_share.train: 1 − (union of device op intervals ÷ traced
window), averaged over the chips used."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
