"""Residency: seconds the program spent building IVF indexes
(`ops.ann.IVF_STATS`' `build_s`: k-means and the assignment on the device,
the balanced fill on the host), a part of `promote_s`. Read from the
program's counter as it stands (the build is over before the window opens,
so the window's delta would read 0); a program without it reports nothing."""

import vectorsearch_roofline


def read(ctx):
    built = vectorsearch_roofline.build_readout()
    return None if built is None else built["build_s"]
