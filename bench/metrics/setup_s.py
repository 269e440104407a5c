"""Seconds from process start to the start of the first timed answer,
less the chip's attach (``jax.devices()``, libtpu's start): imports,
graph generation, relabelling, orientation and warm-up, with every
compile or compile-cache load they need."""


def read(run):
    return run.setup_s
