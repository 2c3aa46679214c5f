"""Device time of prefill per real prompt token in the saturated cells
(``readers.prefill_us_per_tok``), from the trace."""
import readers


def read(ctx):
    return readers.prefill_us_per_tok(ctx)
