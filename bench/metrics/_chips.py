"""Readings over the cell's chips. Each chip's device plane is
``run.planes`` (in device order; one on a one-chip cell), and each chip runs
one shard of every step. A time per step is the mean over the chips; a
roofline share sets the least time of the whole step's work (every shard's
lengths, ``run.steps``) against the sum of the chips' kernel time, so that a
cell of G chips reads the same share as one chip doing its G-th of the
step."""


def mean(run, ns_of_plane):
    """Mean over the chips of ``ns_of_plane(plane)``."""
    return total(run, ns_of_plane) / len(run.planes)


def total(run, ns_of_plane):
    """Sum over the chips of ``ns_of_plane(plane)``."""
    return sum(ns_of_plane(p) for p in run.planes)
