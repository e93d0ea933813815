"""Process start to the window's open: imports, weights from the seed,
compiles or compile-cache reads, warm-up and the lanes' fill."""


def read(v):
    return v.setup_s
