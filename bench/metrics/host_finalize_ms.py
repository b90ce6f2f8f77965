"""Host finalize and pack per query (the program's own `finalize_s`),
in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "finalize_s"))
