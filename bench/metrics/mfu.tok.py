from bench import layers


def read(ctx):
    return layers.mfu(ctx, "tokens")
