from bench import layers


def read(ctx):
    return layers.host_ms(ctx, "images")
