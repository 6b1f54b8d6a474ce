"""warmup_s: seconds per request of ``plan.warmup``, the launch's
untimed first call, which runs the program once more than ``runs``."""
from bench import spans


def read(ctx):
    return spans.per_request(ctx, {"plan.warmup"})
