"""Process start to the first timed round: corpus, loader, JAX start,
compiles or compile-cache loads, and warm-up rounds."""


def read(m):
    return m["setup_s"]
