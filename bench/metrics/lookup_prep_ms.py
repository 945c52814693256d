"""Device time per batch of the fused lookup's index preparation (each
slot's indices taken, remapped to local rows and to cache positions: the
ops under the program's ``lookup_prep`` name scope), on the busiest chip."""
from bench.program_trace import scope_ms


def read(ctx):
    return scope_ms(ctx, "lookup_prep")
