"""setup_s (s, lower is better; host clock): process start until the
traffic starts — loading, building the weights on the device, the engine,
and compiling or loading every program the window runs."""


def read(run):
    return run.setup_s
