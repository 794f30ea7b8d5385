"""setup_s: seconds from the process's start to the window's start
(imports, weights made on the device, engine built, every shape warmed
up or compiled, traffic made)."""


def read(run):
    return run.setup_s
