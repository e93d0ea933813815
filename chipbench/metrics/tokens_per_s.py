"""Output tokens harvested in the window over the window's seconds."""


def read(v):
    return sum(1 for _ in v.window_tokens()) / v.seconds
