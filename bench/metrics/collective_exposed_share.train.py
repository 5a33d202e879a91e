"""collective_exposed_share.train: the share of the traced window in
which a collective (all-reduce, collective-permute, ...) runs on a chip
and no other op runs there, averaged over the chips used.  Nothing when
the trace holds no collective."""


def read(ctx):
    share = ctx["trace"].collective_exposed_share
    return None if share is None else 100.0 * share
