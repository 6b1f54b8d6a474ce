"""assemble_s: seconds per request of host assembly, from the program's
spans: ``plan.draw`` (the seeded table and value draws, absolute
indices) and ``plan.stack`` (the padded batch buffers and the copies
into them)."""
from bench import spans


def read(ctx):
    return spans.per_request(ctx, {"plan.draw", "plan.stack"})
