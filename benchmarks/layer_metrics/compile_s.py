"""Seconds the program's compile plane spent compiling during set-up
(``compile_stats()``): tens of seconds in a checkout's first run, near nothing
once the persistent cache holds the step."""


def read(ctx):
    return float(ctx["facts"]["compile"]["setup_compile_s"])
