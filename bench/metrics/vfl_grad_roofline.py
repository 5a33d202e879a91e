"""vfl_grad_roofline: the least time of the traced ``vfl_grad`` Mosaic
calls, each max(ops / peak FLOP/s, bytes / peak B/s) at the logical
extents (the minibatch or all rows, each party's own columns; the
program's padding to 128 lanes is not counted as work), over the sum of
their device times.  Nothing when no call was traced."""


def read(ctx):
    calls = ctx["trace"].mosaic_calls()
    if not calls:
        return None
    return ctx["trace"].roofline_share(calls, ctx["peaks"],
                                       ctx["kernel_rows"],
                                       ctx["party_widths"])
