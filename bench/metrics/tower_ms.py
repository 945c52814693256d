"""Device time per batch of the DLRM tower (bottom MLP, interaction, top
MLP: the ops under the program's ``tower`` name scope), on the busiest
chip."""
from bench.program_trace import scope_ms


def read(ctx):
    return scope_ms(ctx, "tower")
