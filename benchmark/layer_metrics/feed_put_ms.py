"""Median ``feed_put`` span of the window, on the prefetch feeder's thread:
staging one host batch on the device(s) (``device_put``; 77 MB a chip for the
image model). It blocks when the transfer buffers are busy."""

from benchmark import timeline

NAME = "feed_put_ms"
UNIT = "ms"
LAYER = "Input pipeline"
MOVES = "throughput"
SOURCE = "program_span"


def read(run):
    return timeline.median_span_ms(run, "feed_put")
