"""Set-up: process start to the first timed step or request (init, traffic
pool, tracker warm-up, compiles), on the host clock."""


def read(ctx):
    return ctx["run"]["setup_s"]
