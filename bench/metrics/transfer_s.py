"""transfer_s: seconds per request of host-device copies, from the
program's spans: ``plan.to_device`` (the batch buffers to the device, up
to their readiness) and ``plan.fetch`` (the output back, for digests)."""
from bench import spans


def read(ctx):
    return spans.per_request(ctx, {"plan.to_device", "plan.fetch"})
