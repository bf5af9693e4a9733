"""Traffic kind ``open_loop``: requests sent on the mix's schedule whether or
not earlier ones have finished, each timed from when it was due; arrivals
stop with the window and a bounded drain follows."""

from ._serving import serve


def run(cell):
    return serve(cell, open_loop=True)
