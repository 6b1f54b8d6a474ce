"""keep_mask_s: seconds per request of the store scatter's host
last-write-wins mask (``plan.keep_mask``: the stable argsort of the
indices)."""
from bench import spans


def read(ctx):
    return spans.per_request(ctx, {"plan.keep_mask"})
