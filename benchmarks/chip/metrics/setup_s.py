"""Set-up: process start to the first timed round or step, compilation
(or loading it from the persistent cache) included."""


def read(ctx):
    return ctx.out["setup_s"]
