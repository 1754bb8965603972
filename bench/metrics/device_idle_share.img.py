from bench import layers


def read(ctx):
    return layers.idle_share(ctx, "images")
