"""Device time of prefill per real prompt token in the chat cell
(``readers.prefill_us_per_tok``), from the trace: each admission's prefill
sits inside one gap between the tokens of every request in flight."""
import readers


def read(ctx):
    return readers.prefill_us_per_tok(ctx)
