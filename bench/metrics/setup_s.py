"""setup_s: seconds from process start to the window's first send:
imports, device start-up, the daemon and its disk tier, and the warm-up
(compiles too, in a checkout's first run)."""


def read(ctx):
    return ctx.setup_s
