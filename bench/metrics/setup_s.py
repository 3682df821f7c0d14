"""setup_s: seconds from process start to the window's opening (loading,
weights, engine, compiling or loading every step shape, the traffic's own
set-up)."""


def read(run):
    return run.setup_s
