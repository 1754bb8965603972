from bench import idle_phases


def read(ctx):
    return idle_phases.idle_ms(ctx, "tokens", "sync_wait")
