"""Host prep per query (`vectorsim._prep_sweep`, the program's own
`prep_s`), in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "prep_s"))
