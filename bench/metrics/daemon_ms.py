"""daemon_ms: milliseconds per request in spatterd's handler thread,
from its spans: ``spatterd.parse`` (JSON, request schema, patterns,
mesh), ``spatterd.plan`` (plan, work units, submit) and
``spatterd.respond`` (stats, response, JSON, socket write)."""
from bench import spans

DAEMON = {"spatterd.parse", "spatterd.plan", "spatterd.respond"}


def read(ctx):
    s = spans.per_request(ctx, DAEMON)
    return None if s is None else 1e3 * s
