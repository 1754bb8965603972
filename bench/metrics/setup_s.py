def read(ctx):
    """Process start to the window's first step: imports, device start-up,
    the job, the weights, compiles (or compile-cache loads), the three
    checked steps and the warm steps."""
    return ctx.setup_s
