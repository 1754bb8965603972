from bench import layers


def read(ctx):
    return layers.rate(ctx, "images")
