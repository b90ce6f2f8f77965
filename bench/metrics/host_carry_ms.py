"""Host time per query deciding, at each cut, which jobs commit and what
the carried ones take into the next page (the program's own `carry_s`
span, `vs:carry` in `vectorsim._run_paged`), in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "carry_s"))
