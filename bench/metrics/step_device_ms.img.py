from bench import layers


def read(ctx):
    return layers.step_device_ms(ctx, "images")
