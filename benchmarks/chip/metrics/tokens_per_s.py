"""Training throughput: the tokens of the steps completed in the window
over the time from the window's start to the last of them (host clock)."""


def read(ctx):
    done = ctx.out["completions"]
    if not done:
        return None
    return len(done) * ctx.out["tokens_per_step"] / (done[-1] - ctx.out["t0"])
