"""digest_s: seconds per request of ``plan.digest``: trimming each
member's output, its sha256, and its per-pattern result."""
from bench import spans


def read(ctx):
    return spans.per_request(ctx, {"plan.digest"})
