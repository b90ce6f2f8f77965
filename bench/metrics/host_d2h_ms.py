"""Host time per query copying the engine's outputs back to numpy (the
program's own `d2h_s` span inside `vectorsim._dispatch`), in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "d2h_s"))
