"""Milliseconds a step the input pipeline's producer thread spent making
meta-batches: the program's host span ``dif.pipeline.produce`` around
sampling and preparing each one, clipped to the traced window, over the
traced steps (``bench/scopes.py``)."""


def read(run):
    return run.produce_ms
