from bench import layers


def read(ctx):
    return layers.input_device_ms(ctx, "images")
