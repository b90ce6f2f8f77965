"""Host time per query calling the compiled engine until the call
returns (the program's own `launch_s` span inside `vectorsim._dispatch`),
in ms."""
from bench.readers import ms, stat_mean


def read(run):
    return ms(stat_mean(run, "run", "launch_s"))
