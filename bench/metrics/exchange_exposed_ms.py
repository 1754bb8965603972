from bench import layers


def read(ctx):
    return layers.exchange_ms(ctx, exposed=True)
