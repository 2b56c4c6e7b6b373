"""Served rounds per second: the rounds completed in the window over the
time from the window's start to the last of them (host clock, completion
stamps from ``on_round``)."""


def read(ctx):
    done = ctx.out["completions"]
    return len(done) / (done[-1] - ctx.out["t0"]) if done else None
