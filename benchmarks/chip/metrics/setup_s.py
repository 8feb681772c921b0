"""Seconds from process start to the window's first query: corpus
generation, load (k-means, encode, layout, upload) and the warm-up of the
cell's plan buckets (compiles or cache reads)."""


def read(run):
    return run.setup_s
