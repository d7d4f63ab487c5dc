"""jit: programs compiled, or loaded from the persistent cache, inside the
window. Each is a stall a request waits out; a steady cell reads 0."""


def read(m):
    return float(len(m.compiles))
